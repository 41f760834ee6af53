"""The documents name only files that exist.

A document that teaches a tool nobody can run is how the repository
came to describe a second yardstick beside the benchmark: every path
that README.md, docs/*.md, COVERAGE.md and the verify skill name in
code (inline backticks or a fenced block) and that ends in ``.py`` or
``.sh`` must be in the tree. ROADMAP.md, PERF.md and CHANGES.md are
histories and may name what is gone.
"""
import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

DOCUMENTS = [
    'README.md', 'COVERAGE.md', '.claude/skills/verify/SKILL.md',
    'docs/architecture.md', 'docs/debugging.md', 'docs/env_vars.md',
    'docs/get_started.md', 'docs/migration.md', 'docs/observability.md',
    'docs/reliability.md', 'docs/serving.md',
]

# a path from the root of the repository, or a bare file name (a
# top-level script such as `chip_smoke.py`; `executor.py` for
# `mxnet_tpu/executor.py` is also written so)
_ROOTED = re.compile(
    r'(?<![\w/.-])((?:tools|mxnet_tpu|tests|benchmark|examples)'
    r'/[\w./-]+\.(?:py|sh))\b')
_BARE = re.compile(r'(?<![\w/.*-])([\w-]+\.(?:py|sh))\b')
# bare names that are outside the tree by design: a reader's own script,
# and the upstream reference's examples that tests/compat runs verbatim
# from the reference checkout
_NOT_OURS = re.compile(r'your_\w+\.py|train_cifar10\.py|infer_sort\.py')


def _code(text):
    """The text inside fenced blocks and inline backticks."""
    parts = text.split('```')
    for i, part in enumerate(parts):
        if i % 2:
            yield part
        else:
            yield from re.findall(r'`([^`\n]+)`', part)


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set(os.listdir(REPO))
    for top in ('mxnet_tpu', 'tools', 'tests', 'benchmark', 'examples',
                'python', 'amalgamation', 'cpp-package'):
        for _, _, files in os.walk(os.path.join(REPO, top)):
            names.update(files)
    return names


def named_files(text):
    """(rooted paths, bare file names) a document names in code."""
    rooted, bare = set(), set()
    for code in _code(text):
        rooted.update(_ROOTED.findall(code))
        bare.update(_BARE.findall(_ROOTED.sub('', code)))
    return rooted, bare


def test_pattern_sees_a_missing_file():
    rooted, bare = named_files(
        'run `python tools/gone_tool.py a.json b.json`, then\n'
        '```\npython gone.py --x\n```\nand tests/not_code.py, '
        '`tests/unittest/test_documents.py::test_x`, `io/*.py`.')
    assert rooted == {'tools/gone_tool.py',
                      'tests/unittest/test_documents.py'}
    assert bare == {'gone.py'}


@pytest.mark.parametrize('doc', DOCUMENTS)
def test_document_names_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        rooted, bare = named_files(f.read())
    assert rooted or bare, '%s names no file: the pattern broke' % doc
    missing = sorted(p for p in rooted
                     if not os.path.exists(os.path.join(REPO, p)))
    missing += sorted(n for n in bare - _basenames()
                      if not _NOT_OURS.fullmatch(n))
    assert not missing, '%s names files that do not exist: %s' % (
        doc, missing)
