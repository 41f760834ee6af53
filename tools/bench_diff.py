#!/usr/bin/env python
"""Compare two bench artifacts (BENCH_r*.json) and gate on regression.

The bench history is the repo's perf ledger; nothing so far CHECKED it
— a throughput or MFU slide between rounds only surfaced when a human
re-read the numbers. This is the post-bench gate::

    python tools/bench_diff.py old_bench.json new_bench.json

Accepts either the harness wrapper format (the ``parsed`` key holds
the authoritative metric dict) or raw bench stdout (JSON lines — the
LAST parseable line is authoritative, bench.py's own convention).

Compared metrics, with direction and default tolerance:

- ``throughput`` (the headline ``value``)  — lower is a regression (5%)
- ``mfu``                                  — lower is a regression (5%)
- ``xla_temp_bytes``                       — higher is a regression (10%:
  post-donation the number is small enough that assignment-packing
  noise between XLA revisions exceeds the old 5%)
- ``xla_live_bytes`` (steady-state per-dispatch footprint: args + temp
  + outputs minus donated-alias bytes)     — higher is a regression (10%
  — a donation regression shows up here first)
- ``opt_state_bytes_per_device`` (the sharded weight update's
  per-device optimizer-state footprint)   — higher is a regression (10%)
- ``compile_s`` (cold compile)             — higher is a regression (25%,
  compile time is the noisiest of the set)
- ``serving_p99_ms`` (the serving bench's closed-loop request tail
  latency)                                 — higher is a regression (10%)
- ``serving_queue_wait_p50_ms`` (median time a request sits in the
  batcher queue before its dispatch)       — higher is a regression (10%)
- ``final_loss`` (the run ledger's last banked loss,
  telemetry/ledger.py)                     — higher is a regression (5%;
  a non-finite candidate loss is a regression outright — a diverged
  run must not bank as a healthy throughput number)
- ``goodput_pct`` (the goodput ledger's productive share of wall-clock,
  telemetry/goodput.py)                    — lower is a regression (5%:
  the same throughput with more time lost to compile/input/checkpoint
  badput is a worse run even when the step time held)
- ``bytes_on_wire_per_step`` (gradient bytes per sync step, the
  quantized-collectives plane)             — higher is a regression (10%:
  the collective traffic regrew, e.g. compression silently disengaged)
- ``mem_headroom_pct`` (the memory plane's device-bytes safety margin,
  telemetry/memory.py)                     — lower is a regression (10%:
  the program's HBM footprint grew toward the limit even when the step
  time held — the next model tweak OOMs instead of landing)
- ``host_overhead_pct`` (the step timeline's host-side share of the
  step, telemetry/timeline.py)             — higher is a regression (10%:
  host-side work — stats fetch, checkpoint commit, kvstore traffic —
  crept into the step where the device used to overlap it)

A delta past tolerance in the bad direction prints REGRESSION and the
exit code is 1 — wire it straight into CI after a bench round.
Improvements never fail. A metric missing on either side is a SKIP,
rendered in the table and recapped in a trailing note — never a
silent pass (a baseline that predates a metric is visible evidence,
not an accidental green). Runs that are not config-comparable (metric
name, platform, batch or steps_per_call differ — e.g. one round banked
the CPU fallback) are reported and exit 0, because a fallback round is
not evidence of a perf regression; ``--strict`` turns that into exit 3.
"""
import argparse
import json
import math
import sys

# metric -> (extractor, bad_direction, default_tol_pct)
# bad_direction: -1 = a DROP is a regression, +1 = a RISE is one
_DEF_TOL = {'throughput': 5.0, 'mfu': 5.0, 'xla_temp_bytes': 10.0,
            'xla_live_bytes': 10.0,
            'opt_state_bytes_per_device': 10.0, 'compile_s': 25.0,
            'serving_p99_ms': 10.0, 'serving_queue_wait_p50_ms': 10.0,
            'final_loss': 5.0, 'goodput_pct': 5.0,
            'bytes_on_wire_per_step': 10.0, 'mem_headroom_pct': 10.0,
            'host_overhead_pct': 10.0}
_DIRECTION = {'throughput': -1, 'mfu': -1, 'xla_temp_bytes': +1,
              'xla_live_bytes': +1,
              'opt_state_bytes_per_device': +1, 'compile_s': +1,
              'serving_p99_ms': +1, 'serving_queue_wait_p50_ms': +1,
              'final_loss': +1, 'goodput_pct': -1,
              'bytes_on_wire_per_step': +1, 'mem_headroom_pct': -1,
              'host_overhead_pct': +1}
_ORDER = ('throughput', 'mfu', 'xla_temp_bytes', 'xla_live_bytes',
          'opt_state_bytes_per_device', 'compile_s', 'serving_p99_ms',
          'serving_queue_wait_p50_ms', 'final_loss', 'goodput_pct',
          'bytes_on_wire_per_step', 'mem_headroom_pct',
          'host_overhead_pct')


def load_bench(path):
    """The authoritative metric dict out of one bench artifact."""
    with open(path) as f:
        text = f.read()
    try:
        data = json.loads(text)
        if isinstance(data, dict):
            # harness wrapper: {'n':…, 'rc':…, 'parsed': {...}} — or a
            # bare metric dict already. A failed round has parsed=None;
            # its banked JSON line may still be in the log tail
            if 'parsed' in data:
                if isinstance(data['parsed'], dict):
                    return data['parsed']
                for line in reversed(str(data.get('tail') or '')
                                     .strip().splitlines()):
                    try:
                        d = json.loads(line)
                        if isinstance(d, dict) and 'metric' in d:
                            return d
                    except ValueError:
                        continue
                raise SystemExit(
                    'bench_diff: %s is a failed bench round (no parsed '
                    'metric dict, none recoverable from its log tail)'
                    % path)
            return data
    except ValueError:
        pass
    # raw bench stdout: JSON lines, last parseable METRIC line wins —
    # a trailing auxiliary JSON object must not silently replace the
    # bench record and defuse the gate as 'not comparable'
    for line in reversed(text.strip().splitlines()):
        try:
            d = json.loads(line)
            if isinstance(d, dict) and 'metric' in d:
                return d
        except ValueError:
            continue
    raise SystemExit('bench_diff: %s holds no parseable bench JSON'
                     % path)


def _compile_s(rec):
    cc = rec.get('compile_cache') or {}
    for k in ('cold_s', 'compile_s'):
        if cc.get(k) is not None:
            return float(cc[k])
    return None


def extract(rec):
    """{metric: value} for the compared metrics (absent ones omitted)."""
    out = {}
    if rec.get('value') is not None:
        out['throughput'] = float(rec['value'])
    if rec.get('mfu') is not None:
        out['mfu'] = float(rec['mfu'])
    if rec.get('xla_temp_bytes'):
        out['xla_temp_bytes'] = float(rec['xla_temp_bytes'])
    if rec.get('xla_live_bytes'):
        out['xla_live_bytes'] = float(rec['xla_live_bytes'])
    # `is not None`, not truthiness: a stateless optimizer's honest 0
    # must stay gated (a regrowth from 0 is exactly a regression)
    if rec.get('opt_state_bytes_per_device') is not None:
        out['opt_state_bytes_per_device'] = \
            float(rec['opt_state_bytes_per_device'])
    c = _compile_s(rec)
    if c is not None:
        out['compile_s'] = c
    # serving tail latency (bench.py run_serving_bench): higher = a
    # regression in the continuous-batching plane
    if rec.get('serving_p99_ms') is not None:
        out['serving_p99_ms'] = float(rec['serving_p99_ms'])
    # serving queue wait (the tracing plane's per-stage breakdown):
    # a rise means requests sit in the batcher longer before their
    # dispatch — the batching economics regressed even if device
    # latency held
    if rec.get('serving_queue_wait_p50_ms') is not None:
        out['serving_queue_wait_p50_ms'] = \
            float(rec['serving_queue_wait_p50_ms'])
    # the run ledger's last banked loss (bench feeds telemetry/ledger):
    # convergence gate next to the throughput gates — a faster step
    # that stopped learning is a regression
    if rec.get('final_loss') is not None:
        out['final_loss'] = float(rec['final_loss'])
        # not a gated metric — comparability context for final_loss
        # (bench scales its step count to measured throughput)
        if rec.get('final_loss_step') is not None:
            out['final_loss_step'] = int(rec['final_loss_step'])
    # goodput (telemetry/goodput.py): the productive share of the bench
    # process's wall-clock — a DROP is the regression (more badput)
    if rec.get('goodput_pct') is not None:
        out['goodput_pct'] = float(rec['goodput_pct'])
    # gradient bytes per sync step (parallel/compression.py): a RISE
    # means the collective traffic regrew — e.g. quantization silently
    # disengaged. Improvements (compression landing) never fail; a
    # baseline that predates the gauge is a visible skip.
    if rec.get('bytes_on_wire_per_step') is not None:
        out['bytes_on_wire_per_step'] = \
            float(rec['bytes_on_wire_per_step'])
    # device-bytes headroom (telemetry/memory.py): a DROP means the
    # footprint crept toward the limit — the regression that OOMs the
    # NEXT change rather than this one
    if rec.get('mem_headroom_pct') is not None:
        out['mem_headroom_pct'] = float(rec['mem_headroom_pct'])
    # host-side share of the step (telemetry/timeline.py): a RISE means
    # fetch/checkpoint/kvstore work stopped overlapping the device —
    # the step got slower for a reason throughput alone may hide
    if rec.get('host_overhead_pct') is not None:
        out['host_overhead_pct'] = float(rec['host_overhead_pct'])
    return out


def comparability(a, b):
    """Reasons the two runs are not config-comparable ([] = they are).
    A CPU-fallback round (r02/r04 in the bench history) must not read
    as a 'regression' against a device round."""
    reasons = []
    for key in ('metric', 'platform', 'batch', 'steps_per_call'):
        va, vb = a.get(key), b.get(key)
        if va != vb:
            reasons.append('%s: %r vs %r' % (key, va, vb))
    return reasons


def diff(old, new, tols):
    """Rows [(metric, old, new, delta_pct, tol_pct, verdict)] — verdict
    'REGRESSION' when past tolerance in the bad direction."""
    mo, mn = extract(old), extract(new)
    rows = []
    for metric in _ORDER:
        vo, vn = mo.get(metric), mn.get(metric)
        if vo is None or vn is None:
            if vn is not None:
                # no baseline: the candidate carries a metric the old
                # round never banked — gate-able only from next round
                rows.append((metric, vo, vn, None, tols[metric],
                             'skipped (no baseline)'))
            elif vo is not None:
                rows.append((metric, vo, vn, None, tols[metric],
                             'skipped (missing in new run)'))
            continue
        if not math.isfinite(vn):
            # a nan/inf candidate (a diverged run's final_loss) can
            # never pass a tolerance comparison by accident
            rows.append((metric, vo, vn, None, tols[metric],
                         'REGRESSION (non-finite)'))
            continue
        if not math.isfinite(vo):
            # a nan baseline (a diverged run got banked) can't gate
            # anything: a visible skip, never an 'ok' from a nan delta
            rows.append((metric, vo, vn, None, tols[metric],
                         'skipped (baseline non-finite)'))
            continue
        if metric == 'final_loss':
            so, sn = mo.get('final_loss_step'), mn.get('final_loss_step')
            if so is not None and sn is not None and so != sn:
                # the runs trained different step counts (bench scales
                # steps to measured throughput): a loss delta here
                # conflates convergence with speed — skip, visibly
                rows.append((metric, vo, vn, None, tols[metric],
                             'skipped (trained %d vs %d steps)'
                             % (so, sn)))
                continue
        if vo:
            delta = (vn - vo) / vo * 100.0
        else:
            # a 0 baseline (e.g. a stateless optimizer's opt-state
            # bytes): any nonzero appearance is an infinite rise, not
            # a silent 0% delta
            delta = float('inf') if vn > 0 else 0.0
        bad = delta * _DIRECTION[metric] > tols[metric]
        rows.append((metric, vo, vn, delta, tols[metric],
                     'REGRESSION' if bad else 'ok'))
    return rows


def _fmt_v(v):
    if v is None:
        return '-'
    if abs(v) >= 1e6:
        return '%.3e' % v
    return ('%.4f' % v).rstrip('0').rstrip('.')


def render(rows, old_path, new_path):
    lines = ['bench diff: %s -> %s' % (old_path, new_path),
             '  %-26s %14s %14s %9s %7s  %s'
             % ('metric', 'old', 'new', 'delta%', 'tol%', 'verdict')]
    for metric, vo, vn, delta, tol, verdict in rows:
        lines.append('  %-26s %14s %14s %9s %7s  %s'
                     % (metric, _fmt_v(vo), _fmt_v(vn),
                        '-' if delta is None else '%+.1f' % delta,
                        '%.1f' % tol, verdict))
    return '\n'.join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='Compare two BENCH_r*.json artifacts (throughput, '
                    'MFU, XLA temp bytes, per-device opt-state bytes, '
                    'cold compile time) with per-metric tolerance; '
                    'non-zero exit on regression — the post-bench CI '
                    'gate.')
    ap.add_argument('old', help='baseline bench artifact')
    ap.add_argument('new', help='candidate bench artifact')
    ap.add_argument('--tol-pct', type=float, default=None,
                    help='one tolerance (%%) for every metric '
                         '(default: per-metric — throughput/mfu/temp '
                         '5%%, opt-state bytes 10%%, compile 25%%)')
    ap.add_argument('--tol', action='append', default=[],
                    metavar='METRIC=PCT',
                    help='per-metric tolerance override, e.g. '
                         '--tol mfu=2 (repeatable)')
    ap.add_argument('--strict', action='store_true',
                    help='exit 3 when the runs are not '
                         'config-comparable instead of 0')
    args = ap.parse_args(argv)
    tols = dict(_DEF_TOL)
    if args.tol_pct is not None:
        tols = {k: args.tol_pct for k in tols}
    for spec in args.tol:
        name, _, pct = spec.partition('=')
        if name not in tols or not pct:
            ap.error('unknown --tol %r (metrics: %s)'
                     % (spec, ', '.join(sorted(tols))))
        tols[name] = float(pct)
    old, new = load_bench(args.old), load_bench(args.new)
    reasons = comparability(old, new)
    if reasons:
        print('bench_diff: runs are not config-comparable — %s'
              % '; '.join(reasons))
        print('(a CPU-fallback or re-configured round; no regression '
              'verdict is claimable)')
        return 3 if args.strict else 0
    rows = diff(old, new, tols)
    print(render(rows, args.old, args.new))
    skipped = [r for r in rows if r[5].startswith('skipped')]
    if skipped:
        # a skip is visible evidence, never a silent pass: say exactly
        # which metrics went ungated this round and why
        print('note: ungated this round — %s'
              % '; '.join('%s %s' % (r[0], r[5][len('skipped '):])
                          for r in skipped))
    bad = [r for r in rows if r[5].startswith('REGRESSION')]
    if bad:
        print('REGRESSION: %s' % ', '.join(r[0] for r in bad))
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
