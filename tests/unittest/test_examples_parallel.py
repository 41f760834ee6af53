"""Examples stay runnable: the parallel family and the small how-to
scripts (the reference CI runs example scripts the same way, Jenkinsfile tutorial/test_all.sh stages). One file
per family so that the driver's `--dist loadfile` shares them out; each
case is one child process at the smallest config its own assertion needs
(tests/unittest/_example_runner.py)."""
import pytest

from _example_runner import run_example

pytestmark = pytest.mark.convergence

CASES = [
    ('parallel/train_multihost.py', ['--steps', '20']),
    ('parallel/train_long_context.py', ['--steps', '200']),
    ('parallel/train_long_context.py',
     ['--steps', '200', '--attn', 'striped']),
    ('parallel/train_long_context.py',
     ['--steps', '200', '--attn', 'ulysses']),
    ('parallel/train_5d_transformer.py',
     ['--pp', '2', '--dp', '2', '--tp', '2', '--steps', '3', '--seq',
      '8', '--d-model', '16', '--batch', '4', '--vocab', '32']),
    ('rnn/model_parallel_lstm.py',
     ['--steps', '30', '--num-layers', '2', '--num-hidden', '32',
      '--seq-len', '8', '--lr', '0.02']),
    ('memcost/memcost.py', []),
    ('bayesian-methods/sgld.py', ['--steps', '3000']),
    ('dsd/dsd.py', []),
    ('profiler/profiler_demo.py', []),
    ('module/mnist_mlp.py', []),
    ('python-howto/basics.py', []),
    ('quantization/quantize_mlp.py', []),
]


@pytest.mark.parametrize('script,args', CASES)
def test_example_runs(script, args):
    run_example(script, args)
