"""Run ledger: a manifest + bounded scalar timeseries per run, with a
dependency-free native TensorBoard (tfevents) writer.

Two runs happened — which one is better, and why? Answering that needs
three things no other plane records:

- a **manifest** (`manifest` JSONL record, once per run): the resolved
  MXTPU_* flag values, jax version, device kind/platform, mesh
  descriptor and git sha — so "what was different about run B" is a
  dict diff, not archaeology;
- a **scalar timeseries** (`scalars` JSONL records, every
  ``MXTPU_SCALARS_EVERY`` trained steps): loss, learning rate,
  throughput, global + worst-layer gradient statistics
  (telemetry/dynamics.py) and eval metrics — the bounded
  per-step ledger ``tools/run_compare.py`` diffs across runs;
- a **tfevents mirror** (``MXTPU_TFEVENTS_DIR``): every scalar also
  lands as a native TensorBoard event through
  :class:`TfEventsWriter` — a hand-rolled TFRecord/Event protobuf
  encoder (golden-bytes tested, CRC32C included) so
  ``tensorboard --logdir`` works on any run without tensorboardX or
  torch installed. :func:`read_tfevents` is the matching decoder
  (tests, and anything that wants the series back without TensorBoard).

Gating: ``MXTPU_TELEMETRY=1``; scalar records additionally need
``MXTPU_SCALARS_EVERY > 0`` (default 25). Off = the usual cached-bool
no-op.
"""
import json
import logging
import os
import struct
import threading
import time
import collections

__all__ = ['enabled', 'ensure_manifest', 'begin_run', 'note_train_step',
           'note_eval',
           'snapshot_ledger', 'final_loss',
           'TfEventsWriter', 'read_tfevents',
           'crc32c', 'masked_crc', 'MANIFEST_KEYS']

# the manifest fields rolled up by snapshot_ledger, the crashed-run
# reconstruction (tools/telemetry_report.py) and the run-compare
# config diff (tools/run_compare.py) — one list so the three views
# can't drift when a field is added
MANIFEST_KEYS = ('jax_version', 'platform', 'device_kind',
                 'device_count', 'mesh', 'git_sha', 'symbol')

_RECENT_KEEP = 512      # in-memory (step, t, loss) ring for snapshots
_SNAPSHOT_RECENT = 32   # points exposed to /summary & the watch sparkline


# ---------------------------------------------------------------------------
# tfevents: TFRecord framing + Event proto encoding, no dependencies
# ---------------------------------------------------------------------------

def _crc32c_table():
    poly = 0x82F63B78          # Castagnoli, reflected
    table = []
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ poly if c & 1 else c >> 1
        table.append(c)
    return table


_CRC_TABLE = _crc32c_table()


def crc32c(data):
    """CRC-32C (Castagnoli) of ``data`` — the checksum TFRecord framing
    uses; zlib.crc32 is the WRONG polynomial, hence the table here."""
    crc = 0xFFFFFFFF
    for b in data:
        crc = _CRC_TABLE[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


def masked_crc(data):
    """TFRecord's masked CRC: rotate right by 15 and add the magic
    constant (tensorflow/core/lib/hash/crc32c.h)."""
    crc = crc32c(data)
    return ((((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF)


def _varint(n):
    out = bytearray()
    n &= 0xFFFFFFFFFFFFFFFF     # proto int64 wire form of a negative step
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _key(field, wire):
    return _varint((field << 3) | wire)


def _pb_double(field, v):
    return _key(field, 1) + struct.pack('<d', v)


def _pb_float(field, v):
    return _key(field, 5) + struct.pack('<f', v)


def _pb_varint(field, v):
    return _key(field, 0) + _varint(int(v))


def _pb_bytes(field, data):
    if isinstance(data, str):
        data = data.encode('utf-8')
    return _key(field, 2) + _varint(len(data)) + data


def encode_event(wall_time, step=None, file_version=None, scalars=None):
    """One tensorflow.Event message as bytes. ``scalars`` is a
    {tag: float} dict encoded as Summary/Value simple_values — exactly
    the subset ``tensorboard --logdir`` needs for scalar charts."""
    body = _pb_double(1, float(wall_time))
    if step is not None:
        body += _pb_varint(2, int(step))
    if file_version is not None:
        body += _pb_bytes(3, file_version)
    if scalars:
        summary = b''
        for tag in sorted(scalars):
            value = _pb_bytes(1, tag) + _pb_float(2, float(scalars[tag]))
            summary += _pb_bytes(1, value)
        body += _pb_bytes(5, summary)
    return body


def encode_record(payload):
    """TFRecord framing: u64 length, masked CRC of the length bytes,
    payload, masked CRC of the payload."""
    header = struct.pack('<Q', len(payload))
    return (header + struct.pack('<I', masked_crc(header))
            + payload + struct.pack('<I', masked_crc(payload)))


class TfEventsWriter:
    """Append-only tfevents file writer (``events.out.tfevents.*`` in
    ``logdir``), dependency-free. The first record is the standard
    ``brain.Event:2`` version header; :meth:`add_scalar` appends one
    Event per call. Also usable standalone —
    ``contrib/tensorboard.py``'s LogMetricsCallback falls back to it
    when tensorboardX/torch are absent."""

    _seq = 0
    _seq_lock = threading.Lock()

    def __init__(self, logdir, filename_suffix=''):
        os.makedirs(logdir, exist_ok=True)
        import socket
        # pid + per-process sequence uniquify the name (the
        # tensorboardX convention): two writers born in the same
        # second — the ledger's and the contrib callback's, or two
        # gang workers sharing a logdir — must never append-interleave
        # into one file
        with TfEventsWriter._seq_lock:
            seq = TfEventsWriter._seq
            TfEventsWriter._seq += 1
        name = 'events.out.tfevents.%010d.%s.%d.%d%s' % (
            int(time.time()), socket.gethostname(), os.getpid(), seq,
            filename_suffix)
        self.path = os.path.join(logdir, name)
        self._lock = threading.Lock()
        self._f = open(self.path, 'ab')
        self._write(encode_event(time.time(),
                                 file_version='brain.Event:2'))

    def _write(self, payload):
        with self._lock:
            if self._f is None:
                return
            self._f.write(encode_record(payload))
            self._f.flush()

    def add_scalar(self, tag, value, step):
        """One scalar point (the tensorboardX SummaryWriter method the
        contrib callback calls)."""
        self._write(encode_event(time.time(), step=step,
                                 scalars={str(tag): float(value)}))

    def add_scalars(self, scalars, step, wall_time=None):
        """Several tags at one step in ONE event record."""
        self._write(encode_event(
            wall_time if wall_time is not None else time.time(),
            step=step, scalars=scalars))

    def flush(self):
        with self._lock:
            if self._f is not None:
                self._f.flush()

    def close(self):
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None


# -- reader (tests + offline tooling) ---------------------------------------

def _read_varint(buf, i):
    shift, out = 0, 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _decode_summary(buf):
    scalars = {}
    i = 0
    while i < len(buf):
        key, i = _read_varint(buf, i)
        field, wire = key >> 3, key & 7
        if field == 1 and wire == 2:           # repeated Value
            n, i = _read_varint(buf, i)
            val = buf[i:i + n]
            i += n
            tag, simple = None, None
            j = 0
            while j < len(val):
                vkey, j = _read_varint(val, j)
                vfield, vwire = vkey >> 3, vkey & 7
                if vfield == 1 and vwire == 2:
                    vn, j = _read_varint(val, j)
                    tag = val[j:j + vn].decode('utf-8')
                    j += vn
                elif vfield == 2 and vwire == 5:
                    simple = struct.unpack('<f', val[j:j + 4])[0]
                    j += 4
                else:
                    j = _skip_field(val, j, vwire)
            if tag is not None and simple is not None:
                scalars[tag] = simple
        else:
            i = _skip_field(buf, i, wire)
    return scalars


def _skip_field(buf, i, wire):
    if wire == 0:
        _, i = _read_varint(buf, i)
    elif wire == 1:
        i += 8
    elif wire == 2:
        n, i = _read_varint(buf, i)
        i += n
    elif wire == 5:
        i += 4
    else:
        raise ValueError('unsupported wire type %d' % wire)
    return i


def decode_event(payload):
    """One Event payload -> {'wall_time', 'step', 'file_version',
    'scalars'} (absent fields omitted, scalars {} when none)."""
    out = {'scalars': {}}
    i = 0
    while i < len(payload):
        key, i = _read_varint(payload, i)
        field, wire = key >> 3, key & 7
        if field == 1 and wire == 1:
            out['wall_time'] = struct.unpack('<d', payload[i:i + 8])[0]
            i += 8
        elif field == 2 and wire == 0:
            out['step'], i = _read_varint(payload, i)
        elif field == 3 and wire == 2:
            n, i = _read_varint(payload, i)
            out['file_version'] = payload[i:i + n].decode('utf-8')
            i += n
        elif field == 5 and wire == 2:
            n, i = _read_varint(payload, i)
            out['scalars'] = _decode_summary(payload[i:i + n])
            i += n
        else:
            i = _skip_field(payload, i, wire)
    return out


def read_tfevents(path, verify_crc=True):
    """Decode a tfevents file into a list of event dicts (the
    :func:`decode_event` shape). With ``verify_crc`` a corrupt record
    raises ValueError — the round-trip test's teeth."""
    events = []
    with open(path, 'rb') as f:
        data = f.read()
    i = 0
    while i + 12 <= len(data):
        header = data[i:i + 8]
        (length,) = struct.unpack('<Q', header)
        (hcrc,) = struct.unpack('<I', data[i + 8:i + 12])
        if verify_crc and hcrc != masked_crc(header):
            raise ValueError('tfevents: bad length CRC at offset %d' % i)
        start = i + 12
        if start + length + 4 > len(data):
            break   # truncated tail (a live writer mid-record —
            #         possibly inside the trailing CRC itself)
        payload = data[start:start + length]
        (pcrc,) = struct.unpack('<I',
                                data[start + length:start + length + 4])
        if verify_crc and pcrc != masked_crc(payload):
            raise ValueError('tfevents: bad payload CRC at offset %d'
                             % start)
        events.append(decode_event(payload))
        i = start + length + 4
    return events


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

class _LState:
    __slots__ = ('decided', 'active', 'every', 'step', 'records',
                 'manifest', 'manifest_emitted', 'run_seq', 'writer',
                 'writer_failed', 'last_emit_t', 'last_emit_step', 'lock')

    def __init__(self):
        self.decided = False
        self.active = False
        self.every = 0
        self.step = 0
        self.records = collections.deque(maxlen=_RECENT_KEEP)
        self.manifest = None
        self.manifest_emitted = False
        self.run_seq = 0
        self.writer = None
        self.writer_failed = False
        self.last_emit_t = None
        self.last_emit_step = None
        self.lock = threading.Lock()


_state = _LState()
_decide_lock = threading.Lock()


def _tele():
    from . import enabled as _tele_enabled, _state as st
    _tele_enabled()
    return st


def _decide():
    with _decide_lock:
        if _state.decided:
            return _state.active
        tele_on = _tele().active
        ev = 0
        if tele_on:
            from ..config import flags
            try:
                flags.reload('MXTPU_SCALARS_EVERY')
                ev = int(flags.get('MXTPU_SCALARS_EVERY'))
            except Exception:  # noqa: BLE001 — stripped builds w/o the flag
                ev = 0
        _state.every = ev
        _state.active = tele_on and ev > 0
        _state.decided = True
    return _state.active


def enabled():
    """Whether the scalar ledger is on: MXTPU_TELEMETRY=1 and
    MXTPU_SCALARS_EVERY > 0, decided once."""
    if _state.decided:
        return _state.active
    return _decide()


def _emit(rec):
    st = _tele()
    if st.active and st.sink is not None:
        st.sink.emit(rec)


def _tfevents_dir():
    from ..config import flags
    try:
        flags.reload('MXTPU_TFEVENTS_DIR')
        return flags.get('MXTPU_TFEVENTS_DIR') or ''
    except Exception:  # noqa: BLE001
        return ''


def _writer():
    """The lazy tfevents writer (None when MXTPU_TFEVENTS_DIR unset or
    the open failed — warn once, never crash the fit loop)."""
    if _state.writer is not None or _state.writer_failed:
        return _state.writer
    path = _tfevents_dir()
    if not path:
        _state.writer_failed = True
        return None
    try:
        _state.writer = TfEventsWriter(os.path.expanduser(path))
    except OSError as e:
        _state.writer_failed = True
        logging.warning('ledger: cannot open tfevents dir %s (%s) — '
                        'scalars stay JSONL-only', path, e)
    return _state.writer


# -- manifest ----------------------------------------------------------------

def _git_sha():
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        out = subprocess.run(['git', 'rev-parse', '--short', 'HEAD'],
                             cwd=repo, capture_output=True, text=True,
                             timeout=5)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except Exception:  # noqa: BLE001 — no git / not a checkout
        return None


def _resolved_flags():
    """{name: resolved value} for every declared MXTPU_* flag — the
    run's effective configuration (unparseable values render as their
    raw string so the manifest never raises)."""
    from ..config import flags
    out = {}
    for f in flags:
        try:
            out[f.name] = flags.get(f.name)
        except Exception:  # noqa: BLE001 — a bad env value
            out[f.name] = os.environ.get(f.name)
    return out


def build_manifest(module=None):
    """The run-manifest dict (pure; does not emit)."""
    man = {'pid': os.getpid(), 'argv': list(__import__('sys').argv)}
    try:
        import jax
        man['jax_version'] = jax.__version__
        devs = jax.devices()
        if devs:
            man['platform'] = devs[0].platform
            man['device_kind'] = getattr(devs[0], 'device_kind', None)
            man['device_count'] = len(devs)
    except Exception:  # noqa: BLE001 — backend init can fail; manifest not
        pass
    try:
        from ..parallel import multihost
        man['mesh'] = multihost.mesh_descriptor()
    except Exception:  # noqa: BLE001
        pass
    if module is not None:
        mesh = getattr(getattr(module, '_exec_group', None), 'mesh', None)
        if mesh is not None:
            try:
                man['mesh'] = dict(mesh.shape)
            except Exception:  # noqa: BLE001
                pass
        sym = getattr(module, '_symbol', None)
        if sym is not None:
            man['symbol'] = getattr(sym, 'name', None)
    sha = _git_sha()
    if sha:
        man['git_sha'] = sha
    man['flags'] = _resolved_flags()
    man['env_set'] = sorted(k for k in os.environ
                            if k.startswith('MXTPU_'))
    return man


def ensure_manifest(module=None):
    """Build + emit the `manifest` JSONL record once per process
    (whenever telemetry is on — the manifest is worth one record even
    with the scalar cadence off). Fit boundaries call
    :func:`begin_run` instead, which re-emits per run."""
    st = _tele()
    if not st.active:
        return None
    with _state.lock:
        if _state.manifest_emitted:
            return _state.manifest
        _state.manifest_emitted = True
        _state.run_seq += 1
        seq = _state.run_seq
    return _emit_manifest(module, seq)


def begin_run(module=None):
    """Build + emit a fresh `manifest` record for a new fit() run —
    every in-process fit (and every resilient_fit attempt) gets its
    own, tagged with a monotonically increasing ``run_seq`` so
    tools/run_compare.py and the offline report key on the LATEST
    configuration instead of the process's first. Flags may legally
    change between fits (tests and sweeps flip MXTPU_* between calls),
    so the re-emit is what keeps the ledger honest."""
    st = _tele()
    if not st.active:
        return None
    with _state.lock:
        _state.manifest_emitted = True
        _state.run_seq += 1
        seq = _state.run_seq
    return _emit_manifest(module, seq)


def _emit_manifest(module, seq):
    man = build_manifest(module)
    man['run_seq'] = int(seq)
    _state.manifest = man
    rec = {'type': 'manifest'}
    rec.update(man)
    _emit(rec)
    return man


# -- scalars -----------------------------------------------------------------

def _gauge(name):
    reg = _tele().registry
    g = reg.get(name)
    return g.value if g is not None else None


def _build_record(step, now, loss, lr, extra=None):
    rec = {'type': 'scalars', 'step': int(step)}
    if loss is not None:
        rec['loss'] = round(float(loss), 6)
    if lr is not None:
        rec['lr'] = round(float(lr), 8)
    if _state.last_emit_t is not None and now > _state.last_emit_t \
            and _state.last_emit_step is not None:
        rec['steps_per_sec'] = round(
            (step - _state.last_emit_step) / (now - _state.last_emit_t), 3)
    for field, gauge in (('grad_norm', 'health.grad_norm'),
                         ('samples_per_sec',
                          'speedometer.samples_per_sec')):
        v = _gauge(gauge)
        if v is not None:
            rec[field] = v
    from . import dynamics as _dyn
    if _dyn.enabled():
        dsnap = _dyn.snapshot_dynamics()
        if dsnap:
            if dsnap.get('worst_layer') is not None:
                rec['worst_layer'] = dsnap['worst_layer']
                rec['worst_update_ratio'] = dsnap['worst_update_ratio']
            if dsnap.get('dead_frac_max') is not None:
                rec['dead_frac_max'] = dsnap['dead_frac_max']
    if extra:
        rec.update(extra)
    return rec


def _mirror_tfevents(scalars, step, now):
    """Best-effort tfevents mirror of one scalar dict — shared by the
    train-step and eval paths so the two record streams can't drift."""
    w = _writer()
    if w is None or not scalars:
        return
    try:
        w.add_scalars(scalars, step, wall_time=now)
    except Exception as e:  # noqa: BLE001 — never kill the loop
        logging.debug('ledger: tfevents write failed: %s', e)


def _emit_scalars(rec, now):
    # stamp the CALLER's timestamp: the fused window banks its steps in
    # one burst after the fetch, with per-step times spread over the
    # window (note_train_step's ``t``), and run_compare's step_time /
    # time_to_loss read the record's 't' — the sink's emit-time default
    # would bunch every point of a window at one instant
    rec['t'] = now
    _emit(rec)
    _mirror_tfevents({k: float(v) for k, v in rec.items()
                      if k not in ('type', 'step', 't', 'host',
                                   'worst_layer', 'event', 'epoch')
                      and isinstance(v, (int, float))},
                     rec['step'], now)
    with _state.lock:
        _state.records.append((rec['step'], now, rec.get('loss')))
        _state.last_emit_t = now
        _state.last_emit_step = rec['step']


def note_train_step(loss=None, lr=None, metric=None, t=None):
    """Count one trained step; at every MXTPU_SCALARS_EVERY-th step
    emit a `scalars` record (and its tfevents mirror). ``loss`` is the
    step's loss when the loop knows it (the fused stats path's
    in-graph CrossEntropy); ``metric`` is the running EvalMetric —
    its values land as ``metric_<name>`` fields, and a cross-entropy
    value doubles as the loss when none was given. ``lr`` may be a
    callable (evaluated only on due steps — the per-batch loop's
    scheduler sample must not cost the 24 of 25 non-due steps).
    ``t`` is an explicit wall stamp for callers that process steps in
    a burst after one fetch (the fused window amortizes its steps over
    the window's wall time — emit-time clocks would bunch them)."""
    if not enabled():
        return
    with _state.lock:
        _state.step += 1
        step = _state.step
        due = (step % _state.every) == 0
    if not due:
        return
    if callable(lr):
        lr = lr()
    extra = {}
    if metric is not None:
        try:
            for name, value in metric.get_name_value():
                if value == value:  # skip nan (empty metric)
                    extra['metric_%s' % name] = round(float(value), 6)
                    if loss is None and 'entropy' in name:
                        loss = value
        except Exception:  # noqa: BLE001 — custom metric surprises
            pass
    now = time.time() if t is None else float(t)
    _emit_scalars(_build_record(step, now, loss, lr, extra), now)


def note_eval(name_values, epoch=None):
    """Bank an eval pass's metric values as a `scalars` record
    (``event=eval``, fields ``eval_<name>``) + tfevents ``eval/<name>``
    tags — run_compare's eval-metric column."""
    if not enabled():
        return
    extra = {'event': 'eval'}
    if epoch is not None:
        extra['epoch'] = int(epoch)
    for name, value in name_values:
        if value == value:
            extra['eval_%s' % name] = round(float(value), 6)
    now = time.time()
    with _state.lock:
        step = _state.step
    rec = {'type': 'scalars', 'step': int(step)}
    rec.update(extra)
    _emit(rec)
    _mirror_tfevents({'eval/%s' % k[len('eval_'):]: float(v)
                      for k, v in extra.items()
                      if k.startswith('eval_')}, step, now)


# -- derived metrics ---------------------------------------------------------

def final_loss():
    """The last banked loss, or None."""
    with _state.lock:
        records = list(_state.records)
    for _, _, loss in reversed(records):
        if loss is not None:
            return loss
    return None


def snapshot_ledger():
    """Point-in-time ledger dict for /summary, the summary record and
    the watch sparkline: the manifest (minus the bulky flag dump), the
    last scalar point and a short recent-loss series. None while
    telemetry is off and nothing was recorded."""
    st = _tele()
    if not st.active:
        return None
    with _state.lock:
        man = _state.manifest
        recent = list(_state.records)[-_SNAPSHOT_RECENT:]
        steps = _state.step
        wpath = _state.writer.path if _state.writer is not None else None
    if man is None and not recent and not steps:
        return None
    out = {'steps': int(steps), 'every': int(_state.every)}
    if man is not None:
        out['manifest'] = {k: man.get(k) for k in MANIFEST_KEYS
                           if man.get(k) is not None}
        out['manifest']['env_set'] = man.get('env_set')
        # which in-process fit this manifest belongs to (run_seq stays
        # out of MANIFEST_KEYS: it is identity, not configuration, and
        # run_compare's config diff must not flag it)
        if man.get('run_seq') is not None:
            out['manifest']['run_seq'] = int(man['run_seq'])
    if recent:
        out['recent'] = [{'step': s, 'loss': l} for s, _, l in recent]
        out['last'] = {'step': recent[-1][0], 'loss': recent[-1][2]}
        fl = final_loss()
        if fl is not None:
            out['final_loss'] = fl
    if wpath:
        out['tfevents'] = wpath
    return out


def _reset_for_tests():
    global _state
    if _state.writer is not None:
        try:
            _state.writer.close()
        except Exception:  # noqa: BLE001
            pass
    _state = _LState()
