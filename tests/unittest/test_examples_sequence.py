"""Examples stay runnable: the sequence family (the reference CI runs example
scripts the same way, Jenkinsfile tutorial/test_all.sh stages). One file
per family so that the driver's `--dist loadfile` shares them out; each
case is one child process at the smallest config its own assertion needs
(tests/unittest/_example_runner.py)."""
import pytest

from _example_runner import run_example

pytestmark = pytest.mark.convergence

CASES = [
    ('rnn/lstm_bucketing.py',
     ['--num-epochs', '1', '--batch-size', '16', '--num-hidden', '32',
      '--num-embed', '16', '--num-layers', '1', '--vocab', '50']),
    ('ctc/lstm_ocr.py', ['--epochs', '15']),
    # slow: its own 0.9 sorting-accuracy assertion needs 12 epochs (10 reach
    # 0.82), about two minutes when six workers share the machine
    pytest.param('bi-lstm-sort/lstm_sort.py', ['--epochs', '12'],
                 marks=pytest.mark.slow),
    ('nce-loss/toy_nce.py', ['--epochs', '4']),
    ('cnn_text_classification/train.py', ['--epochs', '3']),
]


@pytest.mark.parametrize('script,args', CASES)
def test_example_runs(script, args):
    run_example(script, args)
