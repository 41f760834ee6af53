#!/usr/bin/env python
"""Watch a live run: a top-style dashboard over the telemetry plane.

Polls the live endpoint a run exposes with ``MXTPU_TELEMETRY=1
MXTPU_TELEMETRY_PORT=<p>`` (telemetry/serve.py) — or tails a JSONL log
when given a file path — and renders throughput, run health and
the per-host cluster spread, refreshing in place::

    python tools/telemetry_watch.py http://tpu-host:9100
    python tools/telemetry_watch.py telemetry.jsonl
    python tools/telemetry_watch.py http://tpu-host:9100 --interval 5
    python tools/telemetry_watch.py http://tpu-host:9100 --once   # one frame

The HTTP mode reads ``/summary`` (the registry snapshot + health +
cluster as JSON); the file mode reuses tools/telemetry_report.py's
loader, so a crashed run's partial log renders too.
"""
import argparse
import json
import os
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
_TOOLS = os.path.dirname(os.path.abspath(__file__))
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

_CLEAR = '\x1b[2J\x1b[H'   # clear screen + home (refresh in place)


def fetch(source):
    """One dashboard input dict (the /summary JSON shape) from an HTTP
    base URL or a JSONL path."""
    if source.startswith(('http://', 'https://')):
        url = source.rstrip('/') + '/summary'
        with urllib.request.urlopen(url, timeout=5) as r:
            return json.loads(r.read().decode('utf-8'))
    import telemetry_report
    records = telemetry_report.load(source)
    summaries = [r for r in records if r.get('type') == 'summary']
    clus = [r for r in records if r.get('type') == 'cluster']
    mems = [r for r in records if r.get('type') == 'memory']
    last_mem = ({k: v for k, v in mems[-1].items()
                 if k not in ('type', 't', 'host')} if mems else None)
    tls = [r for r in records if r.get('type') == 'timeline']
    last_tl = ({k: v for k, v in tls[-1].items()
                if k not in ('type', 't', 'host')} if tls else None)
    if summaries:
        s = summaries[-1]
        return {'elapsed_s': s.get('elapsed_s'),
                'host': s.get('host'),
                'snapshot': s.get('snapshot') or {},
                'programs': s.get('programs'),
                'health': s.get('health'),
                'cluster': s.get('cluster')
                or (clus[-1] if clus else None),
                'memory': s.get('memory') or last_mem,
                'timeline': s.get('timeline') or last_tl,
                'ledger': s.get('ledger')
                or telemetry_report._reconstruct_ledger(records),
                'goodput': s.get('goodput')
                or telemetry_report._reconstruct_goodput(
                    records, s.get('snapshot') or {}, s.get('elapsed_s'),
                    s.get('roofline'),
                    s.get('ledger')
                    or telemetry_report._reconstruct_ledger(records))}
    snapshot, elapsed, programs, health = telemetry_report._reconstruct(
        records)
    led = telemetry_report._reconstruct_ledger(records)
    roofs = [r for r in records if r.get('type') == 'roofline']
    return {'elapsed_s': elapsed, 'host': None, 'snapshot': snapshot,
            'programs': programs, 'health': health,
            'cluster': clus[-1] if clus else None,
            'memory': last_mem,
            'timeline': last_tl,
            'ledger': led,
            'goodput': telemetry_report._reconstruct_goodput(
                records, snapshot, elapsed,
                roofs[-1] if roofs else None, led)}


def _fmt(v, suffix=''):
    if v is None:
        return '-'
    if isinstance(v, float):
        return ('%.3g' % v) + suffix
    return str(v) + suffix


_SPARK = '▁▂▃▄▅▆▇█'


def _sparkline(values):
    """Unicode block sparkline of a numeric series (min..max scaled;
    a flat series renders flat-low)."""
    vals = [float(v) for v in values]
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _SPARK[0] * len(vals)
    return ''.join(_SPARK[min(len(_SPARK) - 1,
                              int((v - lo) / (hi - lo)
                                  * (len(_SPARK) - 1) + 0.5))]
                   for v in vals)


def render(summary, steps_per_s=None, reqs_per_s=None):
    """The dashboard frame for one /summary dict, as a list of lines
    (pure — tested offline). ``steps_per_s`` / ``reqs_per_s`` are the
    poll-to-poll step and serving-request rates the caller measured."""
    snap = summary.get('snapshot') or {}
    c = snap.get('counters', {})
    g = snap.get('gauges', {})
    h = snap.get('histograms', {})
    lines = []
    head = 'mxnet_tpu live telemetry'
    if summary.get('host') is not None:
        head += ' — host %s' % summary['host']
    if summary.get('elapsed_s'):
        head += ' — up %.0fs' % summary['elapsed_s']
    lines.append(head)
    lines.append('')
    steps = c.get('fit.steps')
    rate_bits = []
    if steps is not None:
        rate_bits.append('steps %d' % steps)
    if steps_per_s is not None:
        rate_bits.append('%.2f steps/s' % steps_per_s)
    sps = g.get('speedometer.samples_per_sec') or g.get('eval_samples_per_sec')
    if sps is not None:
        rate_bits.append('%s samples/s' % _fmt(float(sps)))
    lines.append('  throughput   %s' % (', '.join(rate_bits) or '-'))
    fb = h.get('fit.batch')
    if fb and fb.get('count'):
        lines.append('  step_time    p50 %s ms  p95 %s ms'
                     % (_fmt(fb.get('p50')), _fmt(fb.get('p95'))))
    else:
        # fused loop: the dispatch histogram is per-WINDOW (W steps);
        # normalize so the line reads per-step like the cluster rows
        fd = h.get('fused_fit.dispatch')
        w = g.get('fused_fit.steps_per_call')
        if fd and fd.get('count') and fd.get('p50') is not None and w:
            lines.append('  step_time    ~%s ms/step '
                         '(window dispatch p50 / %d)'
                         % (_fmt(float(fd['p50']) / float(w)), int(w)))
    if g.get('fit.input_bound_pct') is not None:
        lines.append('  io_wait      %s%% of loop time'
                     % _fmt(float(g['fit.input_bound_pct'])))
    # goodput line (telemetry/goodput.py): the productive share of
    # wall-clock so far, plus the biggest badput bucket by name — the
    # live twin of the end-of-run "where the time went" block
    good = summary.get('goodput') or {}
    if good.get('goodput_pct') is not None:
        bits = ['%.1f%% productive' % float(good['goodput_pct'])]
        top = good.get('badput_top')
        if top:
            secs = (good.get('buckets') or {}).get(top)
            bits.append('top badput %s%s'
                        % (top, ' (%.1fs)' % secs
                           if isinstance(secs, (int, float)) else ''))
        if good.get('rework_steps'):
            bits.append('%d steps reworked' % int(good['rework_steps']))
        if good.get('job_goodput_pct') is not None:
            bits.append('job %.1f%% across restarts'
                        % float(good['job_goodput_pct']))
        lines.append('  goodput      %s' % ', '.join(bits))
    if g.get('xla.bytes_in_use') is not None:
        lines.append('  device_mem   %.1f MiB live, %.1f MiB peak'
                     % (g['xla.bytes_in_use'] / 2.0**20,
                        (g.get('xla.peak_bytes_in_use')
                         or g['xla.bytes_in_use']) / 2.0**20))
    # memory plane (MXTPU_MEMORY): headroom + steps-to-OOM forecast +
    # the worst layer by attributed peak bytes, from the mem.* gauges
    # or (JSONL mode) the last memory record / summary fold
    mem = summary.get('memory') or {}
    head = g.get('mem.headroom_pct', mem.get('headroom_pct'))
    oom = g.get('mem.steps_to_oom', mem.get('steps_to_oom'))
    worst = g.get('mem.worst_layer', mem.get('worst_layer'))
    ring = g.get('serve.ring_bytes')
    if head is not None or oom is not None or worst is not None \
            or ring is not None:
        bits = []
        if head is not None:
            bits.append('headroom %s%%' % _fmt(float(head)))
        if oom is not None:
            bits.append('~%d steps to OOM' % int(oom))
        if worst is not None:
            wb = g.get('mem.worst_layer_bytes', mem.get('worst_layer_bytes'))
            bits.append('worst layer %s%s'
                        % (worst, ' (%.1f MiB)' % (float(wb) / 2.0**20)
                           if wb is not None else ''))
        if ring is not None:
            bits.append('serve ring %.1f MiB' % (float(ring) / 2.0**20))
        if g.get('mem.pressure', 1 if mem.get('pressure') else None):
            bits.append('MEM_PRESSURE')
        lines.append('  memory       %s' % ', '.join(bits))
    # step timeline (MXTPU_TIMELINE): who gates the gang step and by
    # how much — from the timeline.* gauges or (JSONL mode) the last
    # timeline record / summary fold
    tl = summary.get('timeline') or {}
    crit_host = g.get('timeline.critical_host', tl.get('critical_host'))
    crit_phase = g.get('timeline.critical_phase', tl.get('critical_phase'))
    if crit_host is not None or crit_phase is not None:
        bits = ['critical host %s %s'
                % ('-' if crit_host is None else int(crit_host),
                   crit_phase or '-')]
        skew = g.get('timeline.skew_ms', tl.get('skew_ms'))
        if skew is not None:
            bits.append('skew %s ms/step' % _fmt(float(skew)))
        gs = g.get('timeline.gang_step_ms', tl.get('gang_step_ms'))
        if gs is not None:
            bits.append('gang step %s ms' % _fmt(float(gs)))
        lines.append('  timeline     %s' % ', '.join(bits))
    if g.get('update.opt_state_bytes_per_device') is not None:
        # sharded weight update (MXTPU_SHARDED_UPDATE): whether the
        # ZeRO layout is engaged and what the optimizer state costs
        # per device. The comm share is the STEP's whole collective
        # share (roofline accounting — grad sync + the update's
        # reduce-scatter/all-gather + any tp/pp traffic), labeled as
        # such
        bits = ['%.1f MiB/device'
                % (g['update.opt_state_bytes_per_device'] / 2.0**20),
                'sharded' if g.get('update.sharded')
                else 'replicated']
        if g.get('update.sharded') and g.get('update.dp'):
            bits[-1] += ' dp=%d' % int(g['update.dp'])
        if g.get('roofline.comm_pct_of_step') is not None:
            bits.append('step collectives %s%%'
                        % _fmt(float(g['roofline.comm_pct_of_step'])))
        lines.append('  opt_state    %s' % ', '.join(bits))
    # quantized gradient collectives (MXTPU_GRAD_COMPRESS): bytes per
    # sync step + ratio + mode, with the provenance spelled out —
    # 'measured' is real kvstore wire traffic, 'modeled' is the SPMD
    # window's arithmetic over the leaf layout
    if g.get('comm.bytes_on_wire_per_step') is not None:
        bits = ['%.2f MiB/step'
                % (float(g['comm.bytes_on_wire_per_step']) / 2.0**20)]
        if g.get('comm.compression_ratio') is not None:
            bits.append('%sx compressed'
                        % _fmt(float(g['comm.compression_ratio'])))
        if g.get('comm.mode'):
            bits.append('mode %s' % g['comm.mode'])
        if g.get('comm.bytes_src'):
            bits.append('(%s)' % g['comm.bytes_src'])
        lines.append('  comm         %s' % ', '.join(bits))
    # per-layer training dynamics (MXTPU_DYNAMICS): the layer changing
    # fastest relative to its size + the deadest output, straight from
    # the decimated dynamics.* gauges
    if g.get('dynamics.worst_update_ratio') is not None \
            or g.get('dynamics.dead_frac_max') is not None:
        bits = []
        if g.get('dynamics.worst_update_ratio') is not None:
            bits.append('worst %s dw/w %s'
                        % (g.get('dynamics.worst_layer') or '?',
                           _fmt(float(g['dynamics.worst_update_ratio']))))
        if g.get('dynamics.dead_frac_max') is not None:
            bits.append('dead %.0f%%'
                        % (100.0 * float(g['dynamics.dead_frac_max'])))
        if c.get('dynamics.layer_incidents'):
            n = int(c['dynamics.layer_incidents'])
            bits.append('%d layer incident%s' % (n,
                                                 's' if n != 1 else ''))
        lines.append('  dynamics     %s' % ', '.join(bits))
    # loss sparkline from the run ledger's recent scalars (non-finite
    # points — a diverged run's NaNs — are dropped from the scale)
    import math as _math
    led = summary.get('ledger') or {}
    recent = [p.get('loss') for p in (led.get('recent') or [])
              if isinstance(p.get('loss'), (int, float))
              and _math.isfinite(p['loss'])]
    if recent:
        lines.append('  loss         %s %s (last %d scalars)'
                     % (_fmt(float(recent[-1])), _sparkline(recent),
                        len(recent)))
    if c.get('serve.requests'):
        # serving plane (mxnet_tpu/serving): request rate + latency
        # percentiles + queue/batch state whenever serve.* metrics exist
        bits = ['%d reqs' % int(c['serve.requests'])]
        if reqs_per_s is not None:
            bits.append('%.2f req/s' % reqs_per_s)
        lat = h.get('serve.request_latency') or {}
        p99 = g.get('serve.request_latency_p99_ms')
        if lat.get('p50') is not None:
            bits.append('latency p50 %s ms%s'
                        % (_fmt(lat['p50']),
                           ' / p99 %s ms' % _fmt(float(p99))
                           if p99 is not None else ''))
        if g.get('serve.queue_depth') is not None:
            bits.append('queue %d' % int(g['serve.queue_depth']))
        if g.get('serve.batch_size_p50') is not None:
            bits.append('batch p50 %d' % int(g['serve.batch_size_p50']))
        if g.get('serve.pad_fraction') is not None:
            bits.append('pad %.0f%%' % (100.0
                                        * float(g['serve.pad_fraction'])))
        if c.get('serve.errors'):
            bits.append('%d errors' % int(c['serve.errors']))
        lines.append('  serving      %s' % ', '.join(bits))
        # per-stage latency breakdown (the tracing plane's histograms):
        # where a request's time goes — queue wait vs pad vs the
        # device round (dispatch + blocking fetch)
        qw = (h.get('serve.queue_wait') or {}).get('p50')
        pad = (h.get('serve.pad') or {}).get('p50')
        disp = (h.get('serve.dispatch') or {}).get('p50')
        fetch = (h.get('serve.fetch') or {}).get('p50')
        if qw is not None or pad is not None or disp is not None:
            comp = None
            if disp is not None or fetch is not None:
                comp = float(disp or 0.0) + float(fetch or 0.0)
            lines.append('  stages       queue p50 %s ms, pad p50 %s '
                         'ms, compute p50 %s ms (dispatch+fetch)'
                         % (_fmt(qw), _fmt(pad), _fmt(comp)))
    # SLO plane (telemetry/slo.py): objective, burn, budget — from the
    # slo.* gauges (HTTP and JSONL modes both carry them) or the
    # /summary payload's slo snapshot
    slo = summary.get('slo') or {}
    slo_lat = g.get('slo.latency_objective_ms',
                    slo.get('latency_objective_ms'))
    slo_budget = g.get('slo.error_budget_pct', slo.get('error_budget_pct'))
    if slo_lat is not None or slo_budget is not None:
        bits = []
        if slo_lat is not None:
            bits.append('latency obj %s ms' % _fmt(float(slo_lat)))
        if slo_budget is not None:
            bits.append('err budget %s%%' % _fmt(float(slo_budget)))
        burn = g.get('slo.burn_rate', slo.get('burn_rate'))
        if burn is not None:
            bits.append('burn %sx' % _fmt(float(burn)))
        remaining = g.get('slo.budget_remaining_pct',
                          slo.get('budget_remaining_pct'))
        if remaining is not None:
            bits.append('budget left %s%%' % _fmt(float(remaining)))
        if g.get('slo.degraded') or slo.get('degraded'):
            bits.append('DEGRADED')
        lines.append('  slo          %s' % ', '.join(bits))
    hs = summary.get('health')
    # hang / restart / elastic events render on the health line even
    # when the sentinel plane (MXTPU_HEALTH) is off — they live in
    # plain counters/gauges, so both the HTTP and JSONL modes see them
    restarts = int(c.get('health.restarts')
                   or (hs or {}).get('restarts') or 0)
    hangs = int(c.get('watchdog.hangs') or (hs or {}).get('hangs') or 0)
    shift = g.get('cluster.elastic_shift')
    if hs is not None or restarts or hangs or shift:
        bad = int((hs or {}).get('nonfinite_steps') or 0)
        status = 'ok' if not bad else 'DEGRADED (%d non-finite steps)' % bad
        bits = [status]
        if hangs:
            bits.append('%d hang%s' % (hangs, 's' if hangs != 1 else ''))
        if restarts:
            bits.append('%d restart%s' % (restarts,
                                          's' if restarts != 1 else ''))
        if shift:
            bits.append('shard shift %d' % int(shift))
        lines.append('  health       %s' % ', '.join(bits))
        last = (hs or {}).get('last_anomaly')
        if last:
            lines.append('  last_anomaly %s=%s (baseline %s)'
                         % (last.get('detector', '?'),
                            _fmt(last.get('value')),
                            _fmt(last.get('baseline'))))
    clus = summary.get('cluster')
    if clus:
        lines.append('')
        lines.append('  cluster (%s hosts, spread %s%%, straggler: %s)'
                     % (clus.get('hosts'), _fmt(clus.get('spread_pct')),
                        clus.get('straggler', '-')))
        lines.append('    host   step_ms    io_wait%   dispatch_ms')
        slow = clus.get('slowest_host')
        per = clus.get('per_host') or []
        for r in per:
            mark = '*' if (r.get('host') == slow and len(per) > 1) else ''
            lines.append('    %-5s  %-9s  %-9s  %s'
                         % ('%s%s' % (r.get('host'), mark),
                            _fmt(r.get('step_time_ms')),
                            _fmt(r.get('io_wait_pct')),
                            _fmt(r.get('dispatch_ms'))))
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='Live top-style view of a telemetry endpoint '
                    '(http://host:MXTPU_TELEMETRY_PORT) or JSONL log.')
    ap.add_argument('source', help='endpoint base URL or JSONL path')
    ap.add_argument('--interval', type=float, default=2.0,
                    help='poll interval in seconds (default 2)')
    ap.add_argument('--once', action='store_true',
                    help='render one frame and exit (no screen clear)')
    args = ap.parse_args(argv)
    prev_steps = prev_reqs = prev_t = None
    while True:
        try:
            summary = fetch(args.source)
        except Exception as e:  # noqa: BLE001 — endpoint racing startup
            sys.stderr.write('telemetry_watch: %s\n' % e)
            if args.once:
                return 1
            time.sleep(args.interval)
            continue
        now = time.time()
        counters = (summary.get('snapshot') or {}).get('counters', {})
        steps = counters.get('fit.steps')
        reqs = counters.get('serve.requests')
        rate = req_rate = None
        if None not in (steps, prev_steps, prev_t) and now > prev_t:
            rate = max(0.0, (steps - prev_steps) / (now - prev_t))
        if None not in (reqs, prev_reqs, prev_t) and now > prev_t:
            req_rate = max(0.0, (reqs - prev_reqs) / (now - prev_t))
        prev_steps, prev_reqs, prev_t = steps, reqs, now
        frame = '\n'.join(render(summary, steps_per_s=rate,
                                 reqs_per_s=req_rate))
        if args.once:
            print(frame)
            return 0
        sys.stdout.write(_CLEAR + frame + '\n')
        sys.stdout.flush()
        time.sleep(args.interval)


if __name__ == '__main__':
    sys.exit(main())
