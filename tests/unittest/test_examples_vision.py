"""Examples stay runnable: the vision family (the reference CI runs example
scripts the same way, Jenkinsfile tutorial/test_all.sh stages). One file
per family so that the driver's `--dist loadfile` shares them out; each
case is one child process at the smallest config its own assertion needs
(tests/unittest/_example_runner.py)."""
import pytest

from _example_runner import run_example

pytestmark = pytest.mark.convergence

CASES = [
    ('image-classification/train_mnist.py',
     ['--num-epochs', '1', '--network', 'mlp']),
    ('image-classification/train_imagenet.py',
     ['--num-layers', '18', '--image-shape', '3,32,32', '--num-classes',
      '5', '--samples', '32', '--batch-size', '16', '--benchmark', '1']),
    ('image-classification/benchmark_score.py',
     ['--model', 'resnet18_v1', '--batch-sizes', '2', '--image-size',
      '64']),
    ('image-classification/benchmark_score.py',
     ['--model', 'inception-bn', '--batch-sizes', '2', '--image-size',
      '28']),
    ('rcnn/train_rcnn_lite.py', ['--head-epochs', '10', '--rpn-epochs', '3']),
    ('ssd/train_ssd.py',
     ['--epochs', '40', '--samples', '32', '--batch-size', '16',
      '--min-recall', '0.15']),
    ('gluon/image_classification.py',
     ['--model', 'resnet18_v1', '--epochs', '1', '--samples', '64',
      '--image-size', '16', '--batch-size', '16']),
    ('fcn-xs/fcn_xs.py', ['--epochs', '8']),
    ('neural-style/neural_style.py', ['--steps', '120']),
]


@pytest.mark.parametrize('script,args', CASES)
def test_example_runs(script, args):
    run_example(script, args)
