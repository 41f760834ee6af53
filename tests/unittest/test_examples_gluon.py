"""Examples stay runnable: the gluon family (the reference CI runs example
scripts the same way, Jenkinsfile tutorial/test_all.sh stages). One file
per family so that the driver's `--dist loadfile` shares them out; each
case is one child process at the smallest config its own assertion needs
(tests/unittest/_example_runner.py)."""
import pytest

from _example_runner import run_example

pytestmark = pytest.mark.convergence

CASES = [
    ('gan/dcgan.py',
     ['--epochs', '2', '--samples', '64', '--batch-size', '16']),
    ('gluon/dcgan.py', ['--epochs', '2', '--batches', '8']),
    ('gluon/word_language_model.py',
     ['--tied', '--epochs', '6', '--tokens', '8000']),
    ('gluon/super_resolution.py',
     ['--epochs', '9', '--samples', '96', '--min-psnr', '18']),
    ('gluon/actor_critic.py',
     ['--episodes', '24', '--max-steps', '100', '--target', '30']),
    ('reinforcement-learning/dqn.py',
     ['--episodes', '8', '--train-freq', '4']),
]


@pytest.mark.parametrize('script,args', CASES)
def test_example_runs(script, args):
    run_example(script, args)
