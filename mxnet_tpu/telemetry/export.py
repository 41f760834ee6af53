"""Telemetry exporters: append-only JSONL log + human-readable summary.

The JSONL sink is the machine-readable record a perf investigation
greps after the fact: one JSON object per line, each with a ``type``
('start', 'span', 'compile', 'cache_hit', 'retrace_storm', 'event',
'program', 'oom', 'health', 'anomaly', 'cluster', 'restart', 'hang',
'elastic', 'roofline', 'trace', 'slo', 'flight', 'manifest',
'scalars', 'dynamics', 'goodput', 'memory', 'timeline', 'summary')
and a ``t``
epoch-seconds
stamp —
the full list is documented (and lint-gated) under
MXTPU_TELEMETRY_PATH in docs/env_vars.md. Records buffer in memory and flush every
``_FLUSH_EVERY`` lines (and at shutdown) so the fit loop never blocks
on a per-batch fsync.

``summary_table`` renders a registry snapshot as the end-of-run table
(docs/observability.md).
"""
import json
import logging
import os
import threading
import time

__all__ = ['JsonlSink', 'summary_table']

_FLUSH_EVERY = 64
# ...and at least this often in wall time: the supervisor's liveness
# tier (tools/train_supervisor.py, MXTPU_SUPERVISOR_LIVENESS) watches
# the FILE for growth, so a slow loop whose records sit in the buffer
# must not read as a hang
_FLUSH_SECS = 5.0

# Module-wide count of actual file I/O calls (open/write/flush) — the
# zero-overhead tests assert this stays put while telemetry is off.
_io_calls = 0


class JsonlSink:
    """Append-only JSONL writer; thread-safe, buffered.

    ``host`` (stamped by telemetry.cluster when the sink opens) labels
    every record with this process's host index so multi-host logs
    merge on it. ``max_bytes`` (MXTPU_TELEMETRY_MAX_MB) caps the file:
    once the NEXT record would push the file past the cap, writing
    stops for good — metrics stay live in-process and the
    ``telemetry.dropped_records`` counter keeps the true drop count —
    so a week-long run cannot fill a disk."""

    def __init__(self, path, max_bytes=None):
        global _io_calls
        self.path = path
        self.host = None
        self._lock = threading.Lock()
        self._buf = []
        self._closed = False
        self._max_bytes = max_bytes
        self._capped = False
        self._last_flush = time.time()
        try:
            # append mode: what is already on disk counts against the cap
            self._bytes = os.path.getsize(path)
        except OSError:
            self._bytes = 0
        _io_calls += 1
        self._f = open(path, 'a')

    def _count_dropped(self):
        from . import _state
        if _state.active:
            _state.registry.counter('telemetry.dropped_records').inc()

    def emit(self, record):
        if self._closed:
            return
        record.setdefault('t', time.time())
        if self.host is not None:
            record.setdefault('host', self.host)
        # the flight recorder rides the emit chokepoint: everything
        # headed for the log (including records a capped sink drops)
        # enters the bounded in-memory ring too — one deque append
        from . import flight
        flight.note(record)
        if self._capped:
            self._count_dropped()
            self._heartbeat()
            return
        line = json.dumps(record)
        tripped = False
        raced = False
        with self._lock:
            if self._capped:
                # a concurrent emit tripped the cap between the
                # unlocked check and here — it owns the one warning,
                # this record is just another drop
                raced = True
            elif self._max_bytes is not None and \
                    self._bytes + len(line) + 1 > self._max_bytes:
                self._capped = True
                tripped = True
            else:
                self._bytes += len(line) + 1
                self._buf.append(line)
                if len(self._buf) >= _FLUSH_EVERY or \
                        record['t'] - self._last_flush >= _FLUSH_SECS:
                    self._flush_locked()
        if tripped:
            logging.warning(
                'telemetry: %s reached MXTPU_TELEMETRY_MAX_MB '
                '(%.1f MB) — no further JSONL records will be written; '
                'metrics stay live in-process and '
                'telemetry.dropped_records counts the drops',
                self.path, self._max_bytes / 2.0**20)
        if tripped or raced:
            self._count_dropped()

    def _heartbeat(self):
        """A capped sink appends nothing ever again, but the supervisor
        liveness tier (tools/train_supervisor.py) reads 'file stopped
        changing' as 'child is wedged' — touch the mtime (no growth, so
        the size cap's contract holds) at the flush cadence so a
        healthy-but-capped child is never liveness-killed in a loop."""
        now = time.time()
        if now - self._last_flush < _FLUSH_SECS:
            return
        self._last_flush = now
        try:
            os.utime(self.path)
        except OSError:
            pass

    def _flush_locked(self):
        global _io_calls
        self._last_flush = time.time()
        if self._buf and not self._closed:
            _io_calls += 1
            self._f.write('\n'.join(self._buf) + '\n')
            self._f.flush()
            self._buf = []

    def flush(self):
        with self._lock:
            self._flush_locked()

    def close(self):
        with self._lock:
            self._flush_locked()
            if not self._closed:
                self._closed = True
                self._f.close()


def _fmt(v):
    if v is None:
        return '-'
    if isinstance(v, float):
        if v != v:   # nan
            return 'nan'
        if abs(v) >= 1e6 or (abs(v) < 1e-3 and v != 0):
            return '%.3e' % v
        return '%.3f' % v
    return str(v)


def _mib(n):
    return '%.1f' % (n / 2.0**20)


def _health_lines(health):
    """The "Run health" block (telemetry.health.snapshot_health's
    dict): non-finite incidents, anomaly counts, the last anomaly and
    the input-bound share, rendered deterministically so the offline
    CLI reproduces the live table byte-for-byte."""
    lines = ['-- run health --']
    n_bad = int(health.get('nonfinite_steps') or 0)
    lines.append('  status            %s'
                 % ('DEGRADED (%d non-finite step%s)'
                    % (n_bad, 's' if n_bad != 1 else '')
                    if n_bad else 'ok'))
    incidents = health.get('incidents') or []
    if incidents:
        first = incidents[0]
        desc = '%s' % first.get('source', '?')
        if first.get('step') is not None:
            desc += ' step %s' % first['step']
        if first.get('window_step') is not None:
            desc += ' (window step %d)' % first['window_step']
        if first.get('first_bad_layer'):
            desc += ': first non-finite symbol %s' % first['first_bad_layer']
        lines.append('  first_incident    %s' % desc)
    counts = health.get('anomaly_counts') or {}
    if counts:
        lines.append('  anomalies         %s'
                     % ', '.join('%s=%d' % (k, counts[k])
                                 for k in sorted(counts)))
    last = health.get('last_anomaly')
    if last:
        lines.append('  last_anomaly      %s=%s (baseline %s)'
                     % (last.get('detector', '?'), _fmt(last.get('value')),
                        _fmt(last.get('baseline'))))
    if health.get('restarts'):
        lines.append('  restarts          %d' % int(health['restarts']))
    if health.get('hangs'):
        lines.append('  hangs             %d' % int(health['hangs']))
    if health.get('input_bound_pct') is not None:
        lines.append('  input_bound_pct   %s'
                     % _fmt(float(health['input_bound_pct'])))
    return lines


def _roofline_lines(roof):
    """The "roofline" block (telemetry.roofline.analyze()'s dict): the
    ranked top-N bottleneck layers — class, achieved/peak %, estimated
    headroom — plus the whole-step communication accounting. Rendered
    deterministically from the dict alone so the offline CLI
    (tools/roofline_report.py) reproduces the live block byte-for-byte
    from the JSONL record."""
    from .roofline import TOP_N
    lines = ['-- roofline: %s (%s) --'
             % (roof.get('program', '?'), roof.get('source', '?'))]
    if roof.get('peak_tflops') is not None:
        lines.append('  device            %s (%s peaks: %s TFLOP/s, %s GB/s)'
                     % (roof.get('device') or '?', roof.get('peaks'),
                        _fmt(float(roof['peak_tflops'])),
                        _fmt(float(roof['peak_hbm_gbs']))
                        if roof.get('peak_hbm_gbs') is not None else '-'))
    else:
        lines.append('  device            %s (no peak table entry — set '
                     'MXTPU_PEAK_TFLOPS/MXTPU_PEAK_HBM_GBS)'
                     % (roof.get('device') or '?'))
    if roof.get('step_time_ms') is not None:
        lines.append('  step_time_ms      %s'
                     % _fmt(float(roof['step_time_ms'])))
    layers = roof.get('layers') or []
    if layers:
        w = max(max(len(str(r.get('layer', '?'))) for r in layers[:TOP_N]),
                len('layer'))
        lines.append('  %-*s  %-14s %8s %10s %12s'
                     % (w, 'layer', 'class', 'roof%', 'time_ms',
                        'headroom_ms'))
        for r in layers[:TOP_N]:
            lines.append('  %-*s  %-14s %8s %10s %12s'
                         % (w, r.get('layer', '?'), r.get('class', '?'),
                            _fmt(r.get('roof_pct')), _fmt(r.get('time_ms')),
                            _fmt(r.get('headroom_ms'))))
        if len(layers) > TOP_N:
            lines.append('  (+%d more layers)' % (len(layers) - TOP_N))
    comm = roof.get('comm')
    if comm:
        line = '  comm              %s MiB/step' % _mib(comm.get('bytes')
                                                        or 0)
        if comm.get('time_ms') is not None:
            line += ', %s ms' % _fmt(float(comm['time_ms']))
        if comm.get('pct_of_step') is not None:
            line += ' = %s%% of step' % _fmt(float(comm['pct_of_step']))
        if comm.get('overlap_pct') is not None:
            line += ', overlap %s%%' % _fmt(float(comm['overlap_pct']))
        ops = comm.get('ops') or {}
        opstr = ', '.join('%s %s MiB' % (k, _mib(ops[k]))
                          for k in sorted(ops))
        line += ' (%s%s)' % (comm.get('source', '?'),
                             ('; ' + opstr) if opstr else '')
        lines.append(line)
    return lines


def _memory_lines(mem):
    """The "memory" block (telemetry.memory.analyze()'s dict): the
    ranked per-layer peak attribution — args/temp/out/alias bytes,
    calibrated to memory_analysis totals — plus the live-bytes
    timeline and the steps-to-OOM forecast. Rendered deterministically
    from the dict alone so the offline CLI (tools/memory_report.py)
    reproduces the live block byte-for-byte from the JSONL record."""
    from .memory import TOP_N
    prog = mem.get('program')
    lines = ['-- memory: %s --' % prog if prog else '-- memory --']
    layers = mem.get('layers') or []
    if layers:
        w = max(max(len(str(r.get('layer', '?'))) for r in layers[:TOP_N]),
                len('layer'))
        lines.append('  %-*s  %9s %9s %9s %9s %10s'
                     % (w, 'layer', 'args_MiB', 'temp_MiB', 'out_MiB',
                        'alias_MiB', 'total_MiB'))
        for r in layers[:TOP_N]:
            lines.append('  %-*s  %9s %9s %9s %9s %10s'
                         % (w, r.get('layer', '?'),
                            _mib(r.get('args') or 0),
                            _mib(r.get('temp') or 0),
                            _mib(r.get('out') or 0),
                            _mib(r.get('alias') or 0),
                            _mib(r.get('total') or 0)))
        if len(layers) > TOP_N:
            lines.append('  (+%d more layers)' % (len(layers) - TOP_N))
    if mem.get('live_bytes') is not None:
        lines.append('  program_live      %s MiB (args %s + temp %s + '
                     'out %s - alias %s)'
                     % (_mib(mem['live_bytes']),
                        _mib(mem.get('args_bytes') or 0),
                        _mib(mem.get('temp_bytes') or 0),
                        _mib(mem.get('output_bytes') or 0),
                        _mib(mem.get('alias_bytes') or 0)))
    if mem.get('bytes_in_use') is not None:
        line = '  device_bytes      %s MiB' % _mib(mem['bytes_in_use'])
        if mem.get('bytes_limit'):
            line += ' of %s MiB' % _mib(mem['bytes_limit'])
        if mem.get('headroom_pct') is not None:
            line += ' (headroom %s%%)' % _fmt(float(mem['headroom_pct']))
        if mem.get('samples'):
            line += ', %d samples' % int(mem['samples'])
        lines.append(line)
    if mem.get('slope_bytes_per_step') is not None:
        line = ('  forecast          %+.0f bytes/step'
                % float(mem['slope_bytes_per_step']))
        if mem.get('steps_to_oom') is not None:
            line += ' -> ~%d steps to OOM' % int(mem['steps_to_oom'])
        lines.append(line)
    if mem.get('pressure'):
        lines.append('  pressure          MEM_PRESSURE (forecast at or '
                     'below MXTPU_MEMORY_OOM_STEPS)')
    return lines


def _ledger_lines(led):
    """The "run ledger" block (telemetry.ledger.snapshot_ledger's
    dict): the manifest roll-up, the scalar cadence and the last
    banked point — rendered deterministically so the offline CLI
    reproduces the live table byte-for-byte."""
    lines = ['-- run ledger --']
    man = led.get('manifest') or {}
    if man:
        bits = []
        if man.get('device_kind') or man.get('platform'):
            dev = man.get('device_kind') or man.get('platform')
            if man.get('device_count'):
                dev += ' x%d' % int(man['device_count'])
            bits.append('device=%s' % dev)
        if man.get('jax_version'):
            bits.append('jax=%s' % man['jax_version'])
        if man.get('git_sha'):
            bits.append('git=%s' % man['git_sha'])
        if man.get('mesh'):
            bits.append('mesh=%s' % json.dumps(man['mesh'],
                                               sort_keys=True))
        if bits:
            lines.append('  manifest          %s' % ', '.join(bits))
        if man.get('env_set'):
            lines.append('  flags_set         %s'
                         % ', '.join(man['env_set']))
    if led.get('steps'):
        lines.append('  scalars           %d steps, every %d'
                     % (int(led['steps']), int(led.get('every') or 0)))
    last = led.get('last')
    if last:
        line = '  last              step %s' % last.get('step')
        if last.get('loss') is not None:
            line += ', loss %s' % _fmt(float(last['loss']))
        if led.get('final_loss') is not None \
                and led['final_loss'] != last.get('loss'):
            line += ' (final_loss %s)' % _fmt(float(led['final_loss']))
        lines.append(line)
    if led.get('tfevents'):
        lines.append('  tfevents          %s' % led['tfevents'])
    return lines


def _goodput_lines(good):
    """The "Where the time went" block (telemetry.goodput's dict): one
    row per bucket with seconds and wall share, the goodput verdict and
    the rework/provenance context — rendered deterministically so the
    offline CLI reproduces the live table byte-for-byte."""
    lines = ['-- where the time went --']
    wall = float(good.get('wall_s') or 0.0)
    buckets = good.get('buckets') or {}
    # canonical bucket order (telemetry.goodput.BUCKETS), without
    # importing the live module: the record carries the order
    order = ('step', 'compile', 'input_wait', 'checkpoint', 'eval',
             'comm', 'rework', 'overhead')
    names = [n for n in order if n in buckets]
    names += [n for n in sorted(buckets) if n not in order]
    for name in names:
        secs = float(buckets[name] or 0.0)
        pct = (100.0 * secs / wall) if wall > 0.0 else 0.0
        label = name
        if name == 'comm' and good.get('comm_source'):
            label = 'comm (%s)' % good['comm_source']
        lines.append('  %-18s  %9ss  %5.1f%%'
                     % (label, _fmt(round(secs, 3)), pct))
    lines.append('  %-18s  %9ss' % ('wall', _fmt(round(wall, 3))))
    verdict = 'goodput           %s%%' % _fmt(good.get('goodput_pct'))
    if good.get('badput_top'):
        verdict += ' (top badput: %s)' % good['badput_top']
    lines.append('  %s' % verdict)
    if good.get('rework_steps'):
        lines.append('  rework_steps      %d' % int(good['rework_steps']))
    if good.get('prior_lost_s'):
        lines.append('  prior_lost        %ss across relaunches -> '
                     'job goodput %s%% of %ss'
                     % (_fmt(good['prior_lost_s']),
                        _fmt(good.get('job_goodput_pct')),
                        _fmt(good.get('job_wall_s'))))
    return lines


def _timeline_lines(tl):
    """The "step timeline" block (telemetry.timeline's attribution
    dict): one decomposition row per host from the last sync round —
    step time split into compute / collective-wait / io / host-side,
    plus the estimated clock offset — then the skew (fastest-host idle
    at the allreduce) and the gating host+phase. Rendered
    deterministically from the dict alone so the offline CLI
    (tools/timeline_report.py) reproduces the live block byte-for-byte
    from the JSONL record."""
    lines = ['-- step timeline --']
    lines.append('  hosts             %s' % tl.get('hosts'))
    per = tl.get('per_host') or []
    if per:
        lines.append('  host   step_ms    compute    collect    io    '
                     '     host_side  offset_ms')
        crit = tl.get('critical_host')
        for r in per:
            mark = '*' if (r.get('host') == crit and len(per) > 1) else ''
            lines.append('  %-5s  %-9s  %-9s  %-9s  %-9s  %-9s  %s'
                         % ('%s%s' % (r.get('host'), mark),
                            _fmt(r.get('step_time_ms')),
                            _fmt(r.get('compute_ms')),
                            _fmt(r.get('collective_ms')),
                            _fmt(r.get('io_ms')),
                            _fmt(r.get('host_ms')),
                            _fmt(r.get('clock_offset_ms'))))
    if tl.get('skew_ms') is not None:
        lines.append('  skew              %s ms/step (fastest-host idle '
                     'at the allreduce)' % _fmt(float(tl['skew_ms'])))
    if tl.get('critical_phase') is not None:
        line = '  critical_path     host %s %s' % (tl.get('critical_host'),
                                                   tl['critical_phase'])
        if tl.get('phase_excess_ms') is not None:
            if (tl.get('hosts') or 1) > 1:
                line += ' (+%s ms/step of skew)' \
                    % _fmt(float(tl['phase_excess_ms']))
            else:
                line += ' (%s ms/step)' % _fmt(float(tl['phase_excess_ms']))
        lines.append(line)
    return lines


def _cluster_lines(cluster):
    """The "Cluster" block (telemetry.cluster.snapshot_cluster's dict):
    one row per host from the last aggregation round, the spread, and
    the straggler classification — rendered deterministically so the
    offline CLI reproduces the live table byte-for-byte."""
    lines = ['-- cluster --']
    lines.append('  hosts             %s' % cluster.get('hosts'))
    per = cluster.get('per_host') or []
    if per:
        lines.append('  host   step_ms    io_wait%   dispatch_ms  live_MiB')
        slow = cluster.get('slowest_host')
        for r in per:
            mark = '*' if (r.get('host') == slow and len(per) > 1) else ''
            lines.append('  %-5s  %-9s  %-9s  %-11s  %s'
                         % ('%s%s' % (r.get('host'), mark),
                            _fmt(r.get('step_time_ms')),
                            _fmt(r.get('io_wait_pct')),
                            _fmt(r.get('dispatch_ms')),
                            _mib(r.get('live_bytes') or 0)))
    if cluster.get('spread_pct') is not None:
        lines.append('  step_time_spread  %s%%'
                     % _fmt(float(cluster['spread_pct'])))
    if cluster.get('straggler'):
        extra = ''
        if cluster.get('slowest_host') is not None and len(per) > 1:
            extra = ' (slowest host %s)' % cluster['slowest_host']
        lines.append('  straggler         %s%s'
                     % (cluster['straggler'], extra))
    return lines


def summary_table(snapshot, elapsed_s=None, programs=None, health=None,
                  cluster=None, roofline=None, ledger=None, goodput=None,
                  memory=None, timeline=None):
    """Registry snapshot -> aligned text table (one block per kind).
    ``programs`` is telemetry.programs.snapshot_programs()'s {name:
    record} — rendered as a per-program cost table (and the redundant
    ``program.<name>.*`` gauges are elided from the gauges block);
    ``health`` is telemetry.health.snapshot_health()'s dict — rendered
    as the "Run health" block; ``cluster`` is
    telemetry.cluster.snapshot_cluster()'s dict — rendered as the
    "Cluster" block (its per-host ``cluster.*`` gauges are elided the
    same way); ``roofline`` is telemetry.roofline.analyze()'s dict —
    rendered as the ranked-bottleneck "roofline" block (the
    ``roofline.*`` gauges are elided the same way); ``ledger`` is
    telemetry.ledger.snapshot_ledger()'s dict — rendered as the
    "run ledger" block (manifest roll-up + last scalars; its
    ``dynamics.*`` per-layer gauges stay in the gauges block);
    ``goodput`` is telemetry.goodput.summarize()'s dict — rendered as
    the "Where the time went" block (the ``goodput.*`` gauges are
    elided the same way); ``memory`` is telemetry.memory.analyze()'s
    dict — rendered as the per-layer-peak "memory" block (the
    ``mem.*`` gauges are elided the same way); ``timeline`` is
    telemetry.timeline's attribution dict — rendered as the
    critical-path "step timeline" block (the ``timeline.*`` gauges
    are elided the same way)."""
    lines = ['== telemetry summary%s ==' %
             (' (%.1fs)' % elapsed_s if elapsed_s is not None else '')]
    counters = snapshot.get('counters', {})
    gauges = snapshot.get('gauges', {})
    hists = snapshot.get('histograms', {})
    if programs:
        # one row per compiled program already carries these values
        gauges = {n: v for n, v in gauges.items()
                  if not n.startswith('program.')}
    if cluster:
        # the Cluster block already carries these values
        gauges = {n: v for n, v in gauges.items()
                  if not n.startswith('cluster.')}
    if roofline:
        # the roofline block already carries these values
        gauges = {n: v for n, v in gauges.items()
                  if not n.startswith('roofline.')}
    if goodput:
        # the "Where the time went" block already carries these values
        gauges = {n: v for n, v in gauges.items()
                  if not n.startswith('goodput.')}
    if memory:
        # the memory block already carries these values
        gauges = {n: v for n, v in gauges.items()
                  if not n.startswith('mem.')}
    if timeline:
        # the step-timeline block already carries these values
        gauges = {n: v for n, v in gauges.items()
                  if not n.startswith('timeline.')}
    if counters:
        lines.append('-- counters --')
        w = max(len(n) for n in counters)
        for name in sorted(counters):
            lines.append('  %-*s  %s' % (w, name, _fmt(counters[name])))
    if gauges:
        lines.append('-- gauges --')
        w = max(len(n) for n in gauges)
        for name in sorted(gauges):
            lines.append('  %-*s  %s' % (w, name, _fmt(gauges[name])))
    if programs:
        lines.append('-- programs --')
        w = max(max(len(n) for n in programs), len('name'))
        lines.append('  %-*s  %8s %10s %10s %10s %9s %9s %9s' %
                     (w, 'name', 'compiles', 'calls', 'flops',
                      'bytes_acc', 'temp_MiB', 'arg_MiB', 'out_MiB'))
        for name in sorted(programs):
            r = programs[name]
            lines.append('  %-*s  %8s %10s %10s %10s %9s %9s %9s' %
                         (w, name, _fmt(r.get('compiles', 0)),
                          _fmt(r.get('dispatches', 0)),
                          _fmt(float(r.get('flops', 0.0))),
                          _fmt(float(r.get('bytes_accessed', 0.0))),
                          _mib(r.get('temp_bytes', 0)),
                          _mib(r.get('argument_bytes', 0)),
                          _mib(r.get('output_bytes', 0))))
    if roofline:
        lines.extend(_roofline_lines(roofline))
    if memory:
        lines.extend(_memory_lines(memory))
    if goodput:
        lines.extend(_goodput_lines(goodput))
    if cluster:
        lines.extend(_cluster_lines(cluster))
    if timeline:
        lines.extend(_timeline_lines(timeline))
    if ledger:
        lines.extend(_ledger_lines(ledger))
    if health:
        lines.extend(_health_lines(health))
    if hists:
        lines.append('-- histograms (ms) --')
        w = max(len(n) for n in hists)
        lines.append('  %-*s  %8s %10s %10s %10s %10s' %
                     (w, 'name', 'count', 'mean', 'p50', 'p95', 'max'))
        for name in sorted(hists):
            st = hists[name]
            lines.append('  %-*s  %8s %10s %10s %10s %10s' %
                         (w, name, _fmt(st['count']), _fmt(st['mean']),
                          _fmt(st['p50']), _fmt(st['p95']),
                          _fmt(st['max'])))
    if len(lines) == 1:
        lines.append('  (no metrics recorded)')
    return '\n'.join(lines)
