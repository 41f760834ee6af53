"""Config/flag system (aux 5.6): env catalog + dmlc-Parameter analog.

Reference: dmlc-core parameter.h semantics (Init validation, ranges,
enums, readable errors) and docs/how_to/env_var.md (flag catalog).
"""
import pytest

from mxnet_tpu.config import Parameter, field, flags


class TestFlags:
    def test_defaults(self):
        flags.reload()
        assert flags.get('MXTPU_ENGINE_WORKERS') == 4
        assert flags.get('MXTPU_ENGINE_TYPE') == 'ThreadedEngine'
        assert flags.get('MXTPU_KVSTORE_BIGARRAY_BOUND') == 1 << 20

    def test_env_parse_and_cache(self, monkeypatch):
        monkeypatch.setenv('MXTPU_ENGINE_WORKERS', '7')
        flags.reload('MXTPU_ENGINE_WORKERS')
        assert flags.get('MXTPU_ENGINE_WORKERS') == 7
        monkeypatch.setenv('MXTPU_ENGINE_WORKERS', '9')
        # cached until reload
        assert flags.get('MXTPU_ENGINE_WORKERS') == 7
        flags.reload('MXTPU_ENGINE_WORKERS')
        assert flags.get('MXTPU_ENGINE_WORKERS') == 9
        flags.reload('MXTPU_ENGINE_WORKERS')

    def test_reference_alias(self, monkeypatch):
        # reference MXNET_* spellings are honored
        monkeypatch.delenv('MXTPU_KVSTORE_BIGARRAY_BOUND', raising=False)
        monkeypatch.setenv('MXNET_KVSTORE_BIGARRAY_BOUND', '4096')
        flags.reload('MXTPU_KVSTORE_BIGARRAY_BOUND')
        assert flags.get('MXTPU_KVSTORE_BIGARRAY_BOUND') == 4096
        flags.reload('MXTPU_KVSTORE_BIGARRAY_BOUND')

    def test_validation_errors(self, monkeypatch):
        monkeypatch.setenv('MXTPU_ENGINE_WORKERS', 'lots')
        flags.reload('MXTPU_ENGINE_WORKERS')
        with pytest.raises(ValueError, match='expected int'):
            flags.get('MXTPU_ENGINE_WORKERS')
        monkeypatch.setenv('MXTPU_ENGINE_WORKERS', '0')
        flags.reload('MXTPU_ENGINE_WORKERS')
        with pytest.raises(ValueError, match='>= 1'):
            flags.get('MXTPU_ENGINE_WORKERS')
        monkeypatch.setenv('MXTPU_ENGINE_TYPE', 'WarpEngine')
        flags.reload('MXTPU_ENGINE_TYPE')
        with pytest.raises(ValueError, match='one of'):
            flags.get('MXTPU_ENGINE_TYPE')
        flags.reload()

    def test_bool_parsing(self, monkeypatch):
        for raw, want in [('1', True), ('true', True), ('0', False),
                          ('false', False), ('', False), ('yes', True)]:
            monkeypatch.setenv('MXTPU_NO_NATIVE', raw)
            flags.reload('MXTPU_NO_NATIVE')
            assert flags.get('MXTPU_NO_NATIVE') is want, raw
        flags.reload()

    def test_undeclared_flag_is_a_bug(self):
        with pytest.raises(KeyError):
            flags.get('MXTPU_DOES_NOT_EXIST')

    def test_describe_catalog(self):
        text = flags.describe()
        assert 'MXTPU_ENGINE_WORKERS' in text
        assert 'MXNET_CPU_WORKER_NTHREADS' in text  # alias documented
        assert 'MXTPU_BACKWARD_DO_MIRROR' in text


class TestParameter:
    def _cls(self):
        class ConvParam(Parameter):
            kernel = field(tuple, required=True)
            num_filter = field(int, required=True, min_value=1)
            stride = field(tuple, (1, 1))
            layout = field(str, 'NCHW', choices={'NCHW', 'NHWC'})
            no_bias = field(bool, False)
        return ConvParam

    def test_init_defaults_and_required(self):
        ConvParam = self._cls()
        p = ConvParam(kernel=(3, 3), num_filter=8)
        assert p.stride == (1, 1) and p.layout == 'NCHW'
        with pytest.raises(ValueError, match='required'):
            ConvParam(kernel=(3, 3))

    def test_validation(self):
        ConvParam = self._cls()
        with pytest.raises(ValueError, match='>= 1'):
            ConvParam(kernel=(3, 3), num_filter=0)
        with pytest.raises(ValueError, match='one of'):
            ConvParam(kernel=(3, 3), num_filter=1, layout='CHWN')
        with pytest.raises(ValueError, match='unknown parameter'):
            ConvParam(kernel=(3, 3), num_filter=1, kernal=(3, 3))

    def test_coercion(self):
        ConvParam = self._cls()
        p = ConvParam(kernel=[3, 3], num_filter='8', no_bias='false')
        assert p.kernel == (3, 3) and p.num_filter == 8
        assert p.no_bias is False

    def test_asdict_repr_roundtrip(self):
        ConvParam = self._cls()
        p = ConvParam(kernel=(3, 3), num_filter=8)
        d = p.asdict()
        assert d['kernel'] == (3, 3)
        p2 = ConvParam(**d)
        assert p2.asdict() == d
        assert 'num_filter=8' in repr(p)

    def test_inheritance_merges_fields(self):
        class Base(Parameter):
            a = field(int, 1)

        class Child(Base):
            b = field(int, 2)

        c = Child(a=5)
        assert c.a == 5 and c.b == 2


def test_libinfo_log_name_modules():
    """Module-path parity: libinfo/log/name (reference python/mxnet/)."""
    import logging
    import mxnet_tpu.libinfo as libinfo
    import mxnet_tpu.log as log
    import mxnet_tpu.name as name_mod
    import mxnet_tpu as mx

    paths = libinfo.find_lib_path()
    assert paths and all(p.endswith('.so') for p in paths)
    assert libinfo.__version__ == mx.__version__

    logger = log.get_logger('mxtpu_test_logger', level=logging.INFO)
    assert logger.level == logging.INFO
    logger2 = log.get_logger('mxtpu_test_logger', level=logging.DEBUG)
    assert logger2 is logger and logger.level == logging.DEBUG
    assert len(logger.handlers) == 1          # no handler duplication

    assert name_mod.NameManager is mx.attribute.NameManager
    with name_mod.Prefix('pfx_'):
        s = mx.sym.FullyConnected(mx.sym.Variable('d'), num_hidden=2)
        assert s.name.startswith('pfx_')


def test_parse_log_tool(tmp_path):
    """tools/parse_log.py over real fit() log lines (reference
    tools/parse_log.py)."""
    import os
    import subprocess
    import sys as _sys
    log = tmp_path / 'train.log'
    log.write_text(
        'INFO Epoch[0] Train-accuracy=0.610000\n'
        'INFO Epoch[0] Time cost=12.500\n'
        'INFO Epoch[0] Validation-accuracy=0.580000\n'
        'INFO Epoch[1] Train-accuracy=0.820000\n'
        'INFO Epoch[1] Time cost=11.900\n'
        'INFO Epoch[1] Validation-accuracy=0.790000\n')
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    out = subprocess.run(
        [_sys.executable, os.path.join(repo, 'tools', 'parse_log.py'),
         str(log), '--format', 'csv'],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    lines = out.stdout.strip().splitlines()
    assert lines[0] == 'epoch,train-accuracy,time,val-accuracy'
    assert lines[1].startswith('0,0.61,12.5,0.58')
    assert lines[2].startswith('1,0.82,11.9,0.79')


def test_env_vars_doc_in_sync_with_flag_catalog():
    """CI gate: every MXTPU_* flag declared in config.py has a
    docs/env_vars.md entry and vice versa — flag docs cannot drift
    (entries are lines of the form 'MXTPU_NAME [type, default ...]';
    prose mentions like MXTPU_SEED, which mxnet_tpu.random reads at
    import, are outside the validated catalog and don't match)."""
    import os
    import re
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(repo, 'docs', 'env_vars.md')) as f:
        doc = f.read()
    documented = set(re.findall(r'^(MXTPU_[A-Z0-9_]+) \[', doc, re.M))
    declared = {f.name for f in flags}
    undocumented = sorted(declared - documented)
    assert not undocumented, (
        'flags declared in config.py but missing from docs/env_vars.md: '
        '%s' % undocumented)
    stale = sorted(documented - declared)
    assert not stale, (
        'docs/env_vars.md entries with no config.py declaration: %s'
        % stale)
    # the catalog stays alphabetized (the doc's stated convention)
    entries = re.findall(r'^(MXTPU_[A-Z0-9_]+) \[', doc, re.M)
    assert entries == sorted(entries), 'env_vars.md entries not sorted'


def test_jsonl_record_types_documented():
    """CI gate: every JSONL record type the telemetry plane emits
    (grep for the `{'type': '<name>'` literal at the emit sites —
    mxnet_tpu plus the framework-free supervisors in tools/) appears
    in docs/env_vars.md's MXTPU_TELEMETRY_PATH type list, and the
    documented list names no type nothing emits — the drift that
    required PR 5's nine-flag backfill (and this PR's trace/slo/flight
    backfill) cannot recur."""
    import glob
    import os
    import re
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    sources = glob.glob(os.path.join(repo, 'mxnet_tpu', '**', '*.py'),
                        recursive=True)
    sources += glob.glob(os.path.join(repo, 'tools', '*.py'))
    emitted = set()
    for src in sources:
        with open(src) as f:
            emitted.update(re.findall(r"\{'type': '([a-z_]+)'", f.read()))
    assert emitted, 'no emit sites found — the grep pattern broke'
    with open(os.path.join(repo, 'docs', 'env_vars.md')) as f:
        doc = f.read()
    m = re.search(r"a 'type' \(([^)]*)\)", doc)
    assert m, 'MXTPU_TELEMETRY_PATH no longer documents the type list'
    documented = set(re.findall(r"'([a-z_]+)'", m.group(1)))
    undocumented = sorted(emitted - documented)
    assert not undocumented, (
        'JSONL record types emitted but missing from the '
        'MXTPU_TELEMETRY_PATH list in docs/env_vars.md: %s'
        % undocumented)
    stale = sorted(documented - emitted)
    assert not stale, (
        'docs/env_vars.md documents JSONL record types nothing '
        'emits: %s' % stale)
