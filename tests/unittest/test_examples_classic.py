"""Examples stay runnable: the classic family (the reference CI runs example
scripts the same way, Jenkinsfile tutorial/test_all.sh stages). One file
per family so that the driver's `--dist loadfile` shares them out; each
case is one child process at the smallest config its own assertion needs
(tests/unittest/_example_runner.py)."""
import pytest

from _example_runner import run_example

pytestmark = pytest.mark.convergence

CASES = [
    ('recommenders/matrix_fact.py', ['--epochs', '4']),
    ('adversary/adversary_generation.py', ['--epochs', '8']),
    ('numpy-ops/custom_softmax.py', ['--epochs', '8']),
    ('svm_mnist/svm_mnist.py', ['--epochs', '10']),
    ('autoencoder/mnist_sae.py',
     ['--pretrain-epochs', '2', '--finetune-epochs', '4']),
    ('vae/vae.py', ['--epochs', '8', '--samples', '256']),
    ('multi-task/example_multi_task.py', ['--epochs', '8']),
    ('sparse/linear_classification.py', []),
    ('stochastic-depth/sd_mnist.py', []),
    ('dec/dec.py', ['--pretrain-epochs', '4', '--dec-iters', '10']),
]


@pytest.mark.parametrize('script,args', CASES)
def test_example_runs(script, args):
    run_example(script, args)
