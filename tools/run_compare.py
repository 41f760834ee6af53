#!/usr/bin/env python
"""Compare two (or more) runs by their telemetry ledgers and gate on
training-dynamics regression.

Two runs happened — did the second one regress, and which layer is
why? Each run's telemetry JSONL (MXTPU_TELEMETRY_PATH, with
``MXTPU_SCALARS_EVERY`` banking the `scalars` timeseries and
``MXTPU_DYNAMICS`` the per-layer `dynamics` records) is a complete
ledger: manifest, loss curve, step times, per-layer dynamics. This
tool diffs them, with a verdict per metric and exit code 1 on a
regression::

    python tools/run_compare.py baseline.jsonl candidate.jsonl

Compared, candidate vs the FIRST path (the baseline):

- ``loss_at_step``   — the loss at the last step both runs banked;
  higher is a regression (default tolerance 5%)
- ``final_loss``     — each run's last banked loss (same direction)
- ``time_to_loss``   — seconds to first reach the target loss
  (``--target-loss``, default: the baseline's final loss); slower is
  a regression (default 20%); a candidate that trained at least as
  many steps but never got there is a regression outright
- ``step_time_ms``   — median wall time per step between scalar
  records; higher is a regression (default 10%)

A candidate whose loss curve goes non-finite (or that recorded
named-layer ``dynamics`` incidents) while the baseline stayed clean is
DIVERGED — exit 1 regardless of tolerances. Improvements never fail;
a metric missing on either side renders as a skip with a trailing
note, never a silent pass. When both runs carry per-layer `dynamics`
records, layers whose update ratio or gradient norm drifted past
``--layer-tol-pct`` are listed and the worst one is named in the
verdict line — the "this run regressed and layer fc2 is why" loop.

Manifest differences (flags, jax version, device) print first: the
config diff is usually the explanation.
"""
import argparse
import math
import os
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
_TOOLS = os.path.dirname(os.path.abspath(__file__))
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

_DEF_TOL = {'loss_at_step': 5.0, 'final_loss': 5.0,
            'time_to_loss': 20.0, 'step_time_ms': 10.0}
# every compared metric regresses UPWARD (loss, seconds, ms)
_ORDER = ('loss_at_step', 'final_loss', 'time_to_loss', 'step_time_ms')


def _finite(v):
    return v is not None and isinstance(v, (int, float)) \
        and math.isfinite(v)


class Run:
    """One run's ledger, extracted from its telemetry JSONL."""

    def __init__(self, path, records):
        self.path = path
        self.label = os.path.basename(path)
        self.manifest = None
        self.scalars = []        # (step, t, loss) train records, step order
        self.evals = []          # eval-event records
        self.dynamics = None     # last per-layer dynamics record
        self.layer_incidents = []
        for r in records:
            typ = r.get('type')
            if typ == 'manifest':
                # a process emits one manifest PER fit (run_seq-tagged);
                # the latest one describes the run this log's final
                # state belongs to
                self.manifest = r
            elif typ == 'scalars':
                if r.get('event') == 'eval':
                    self.evals.append(r)
                elif r.get('step') is not None:
                    self.scalars.append((int(r['step']), r.get('t'),
                                         r.get('loss')))
            elif typ == 'dynamics':
                if r.get('event') == 'layer_nonfinite':
                    self.layer_incidents.append(r)
                elif r.get('layers'):
                    self.dynamics = r
            elif typ == 'summary' and self.manifest is None:
                man = (r.get('ledger') or {}).get('manifest')
                if man:
                    self.manifest = man
        self.scalars.sort(key=lambda p: p[0])

    # -- derived ------------------------------------------------------------
    @property
    def steps(self):
        return self.scalars[-1][0] if self.scalars else None

    def loss_at(self, step):
        """The loss at the last banked point <= step (None without
        one)."""
        best = None
        for s, _, loss in self.scalars:
            if s > step:
                break
            if loss is not None:
                best = loss
        return best

    def final_loss(self):
        for _, _, loss in reversed(self.scalars):
            if loss is not None:
                return loss
        return None

    def nonfinite(self):
        """True when any banked loss is non-finite or a named-layer
        dynamics incident was recorded."""
        if self.layer_incidents:
            return True
        return any(loss is not None and not math.isfinite(loss)
                   for _, _, loss in self.scalars)

    def final_evals(self):
        """{metric_name: value} from each metric's LAST banked
        eval-event record (epoch-end train/val metrics)."""
        out = {}
        for r in self.evals:
            for k, v in r.items():
                if k.startswith('eval_') and isinstance(v, (int, float)):
                    out[k[len('eval_'):]] = v
        return out

    def time_to_loss(self, target):
        if target is None or not self.scalars:
            return None
        t0 = self.scalars[0][1]
        if t0 is None:
            return None
        for _, t, loss in self.scalars:
            if _finite(loss) and loss <= target and t is not None:
                return t - t0
        return None

    def step_time_ms(self):
        """Median wall-ms per step between consecutive scalar
        records."""
        deltas = []
        for (s0, t0, _), (s1, t1, _) in zip(self.scalars,
                                            self.scalars[1:]):
            if t0 is not None and t1 is not None and s1 > s0 \
                    and t1 > t0:
                deltas.append((t1 - t0) / (s1 - s0) * 1e3)
        return statistics.median(deltas) if deltas else None


def load_run(path):
    import telemetry_report
    return Run(path, telemetry_report.load(path))


# ---------------------------------------------------------------------------
# manifest + per-layer diffs
# ---------------------------------------------------------------------------

# per-run output locations: any two comparable runs necessarily differ
# here (two runs can't share one JSONL) — never a config signal, and
# the noise would bury the real flag diff the feature exists to surface
_PER_RUN_FLAGS = frozenset({'MXTPU_TELEMETRY_PATH', 'MXTPU_TFEVENTS_DIR',
                            'MXTPU_XPROF_DIR', 'MXTPU_CKPT_DIR'})


def manifest_diff(base, cand):
    """Lines describing how the candidate's manifest differs — flags
    first (the usual explanation), then environment."""
    from mxnet_tpu.telemetry.ledger import MANIFEST_KEYS
    lines = []
    mb, mc = base.manifest or {}, cand.manifest or {}
    fb, fc = mb.get('flags') or {}, mc.get('flags') or {}
    changed = sorted(k for k in set(fb) | set(fc)
                     if k not in _PER_RUN_FLAGS
                     and fb.get(k) != fc.get(k))
    if changed:
        lines.append('  flags: %s' % '; '.join(
            '%s %r -> %r' % (k, fb.get(k), fc.get(k)) for k in changed))
    for key in MANIFEST_KEYS:
        if mb.get(key) != mc.get(key):
            lines.append('  %s: %r -> %r' % (key, mb.get(key),
                                             mc.get(key)))
    return lines


def layer_drift(base, cand, tol_pct):
    """[(layer, stat, base, cand, delta_pct)] for common layers whose
    grad_norm / update_ratio moved past tol_pct, worst first."""
    if base.dynamics is None or cand.dynamics is None:
        return None
    lb, lc = base.dynamics['layers'], cand.dynamics['layers']
    out = []
    for layer in sorted(set(lb) & set(lc)):
        for stat in ('update_ratio', 'grad_norm'):
            vb, vc = lb[layer].get(stat), lc[layer].get(stat)
            if vc is None and vb is not None:
                out.append((layer, stat, vb, vc, float('inf')))
                continue
            if not _finite(vb) or not _finite(vc) or vb == 0:
                continue
            delta = (vc - vb) / vb * 100.0
            if abs(delta) > tol_pct:
                out.append((layer, stat, vb, vc, delta))
    out.sort(key=lambda r: -abs(r[4]))
    return out


# ---------------------------------------------------------------------------
# the diff
# ---------------------------------------------------------------------------

def extract(run, last_common, target):
    out = {}
    v = run.loss_at(last_common) if last_common is not None else None
    if v is not None:
        out['loss_at_step'] = v
    v = run.final_loss()
    if v is not None:
        out['final_loss'] = v
    v = run.time_to_loss(target)
    if v is not None:
        out['time_to_loss'] = v
    v = run.step_time_ms()
    if v is not None:
        out['step_time_ms'] = v
    return out


def diff(base, cand, tols, target):
    last_common = None
    if base.steps is not None and cand.steps is not None:
        last_common = min(base.steps, cand.steps)
    mb = extract(base, last_common, target)
    mc = extract(cand, last_common, target)
    rows = []
    for metric in _ORDER:
        vb, vc = mb.get(metric), mc.get(metric)
        if vb is None or vc is None:
            if metric == 'time_to_loss' and vb is not None \
                    and vc is None and cand.steps is not None \
                    and base.steps is not None \
                    and cand.steps >= base.steps:
                # the candidate trained at least as long and never
                # reached the target the baseline reached
                rows.append((metric, vb, vc, None, tols[metric],
                             'REGRESSION (target never reached)'))
            elif vc is not None:
                rows.append((metric, vb, vc, None, tols[metric],
                             'skipped (no baseline)'))
            elif vb is not None:
                rows.append((metric, vb, vc, None, tols[metric],
                             'skipped (missing in candidate)'))
            continue
        if not math.isfinite(vb):
            # a non-finite baseline can't certify anything — render a
            # visible skip (both-sides-NaN lands here too: a diverged
            # baseline is not comparative evidence, same rule as the
            # DIVERGED verdict below)
            rows.append((metric, vb, vc, None, tols[metric],
                         'skipped (baseline non-finite)'))
            continue
        if not math.isfinite(vc):
            rows.append((metric, vb, vc, None, tols[metric],
                         'REGRESSION (non-finite)'))
            continue
        delta = (vc - vb) / vb * 100.0 if vb else \
            (float('inf') if vc > 0 else 0.0)
        bad = delta > tols[metric]
        rows.append((metric, vb, vc, delta, tols[metric],
                     'REGRESSION' if bad else 'ok'))
    return rows, last_common


def _fmt_v(v):
    if v is None:
        return '-'
    if abs(v) >= 1e6:
        return '%.3e' % v
    return ('%.4f' % v).rstrip('0').rstrip('.')


def render(rows, base, cand, last_common):
    head = 'run compare: %s -> %s' % (base.label, cand.label)
    if last_common is not None:
        head += ' (last common step %d)' % last_common
    lines = [head,
             '  %-16s %14s %14s %9s %7s  %s'
             % ('metric', 'baseline', 'candidate', 'delta%', 'tol%',
                'verdict')]
    for metric, vb, vc, delta, tol, verdict in rows:
        lines.append('  %-16s %14s %14s %9s %7s  %s'
                     % (metric, _fmt_v(vb), _fmt_v(vc),
                        '-' if delta is None else '%+.1f' % delta,
                        '%.1f' % tol, verdict))
    return '\n'.join(lines)


def compare_pair(base, cand, tols, target, layer_tol):
    """Print one baseline->candidate comparison; returns True when the
    candidate regressed/diverged."""
    man = manifest_diff(base, cand)
    if man:
        print('config diff (%s -> %s):' % (base.label, cand.label))
        for line in man:
            print(line)
    rows, last_common = diff(base, cand, tols, target)
    print(render(rows, base, cand, last_common))
    skipped = [r for r in rows if r[5].startswith('skipped')]
    if skipped:
        print('note: ungated — %s'
              % '; '.join('%s %s' % (r[0], r[5][len('skipped '):])
                          for r in skipped))
    ev_b, ev_c = base.final_evals(), cand.final_evals()
    common = sorted(set(ev_b) & set(ev_c))
    if common:
        # informational (no verdict: metric direction isn't knowable
        # in general — accuracy rises, cross-entropy falls)
        print('eval metrics (last banked):')
        for name in common:
            vb, vc = ev_b[name], ev_c[name]
            print('  %-24s %12s -> %-12s %s'
                  % (name, _fmt_v(vb), _fmt_v(vc),
                     '%+.1f%%' % ((vc - vb) / vb * 100.0) if vb else '-'))
    bad = [r for r in rows if r[5].startswith('REGRESSION')]
    if base.nonfinite():
        print('warning: baseline %s itself went non-finite — its loss '
              'gates are skipped and cannot certify the candidate'
              % base.label)
    diverged = cand.nonfinite() and not base.nonfinite()
    if diverged:
        why = ''
        if cand.layer_incidents:
            first = cand.layer_incidents[0]
            why = ' — layer %s %s non-finite%s' % (
                first.get('layer', '?'), first.get('stat', '?'),
                ' at step %s' % first['step']
                if first.get('step') is not None else '')
        print('DIVERGED: %s went non-finite%s' % (cand.label, why))
    drift = layer_drift(base, cand, layer_tol)
    if drift is None:
        print('note: per-layer dynamics not banked on both sides '
              '(MXTPU_DYNAMICS=1 records them) — layer attribution '
              'unavailable')
    elif drift:
        print('layer drift (> %.0f%%):' % layer_tol)
        for layer, stat, vb, vc, delta in drift[:8]:
            print('  %-24s %-13s %12s -> %-12s %s'
                  % (layer, stat, _fmt_v(vb), _fmt_v(vc),
                     'non-finite' if not math.isfinite(delta)
                     else '%+.1f%%' % delta))
        if bad or diverged:
            worst = drift[0]
            print('worst layer: %s (%s %s)' % (
                worst[0], worst[1],
                'non-finite' if not math.isfinite(worst[4])
                else '%+.1f%%' % worst[4]))
    if bad:
        print('REGRESSION: %s' % ', '.join(r[0] for r in bad))
    return bool(bad) or diverged


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='Diff two or more runs by their telemetry ledgers '
                    '(manifest, scalars timeseries, per-layer dynamics) '
                    'with per-metric tolerance; non-zero exit on a '
                    'regressed or diverged candidate '
                    '(docs/observability.md, "Comparing runs").')
    ap.add_argument('baseline', help='baseline telemetry JSONL')
    ap.add_argument('candidates', nargs='+',
                    help='candidate telemetry JSONL(s), each compared '
                         'against the baseline')
    ap.add_argument('--tol-pct', type=float, default=None,
                    help='one tolerance (%%) for every metric (default: '
                         'per-metric — loss 5%%, time-to-loss 20%%, '
                         'step time 10%%)')
    ap.add_argument('--tol', action='append', default=[],
                    metavar='METRIC=PCT',
                    help='per-metric tolerance override, e.g. '
                         '--tol final_loss=2 (repeatable)')
    ap.add_argument('--target-loss', type=float, default=None,
                    help='time-to-loss target (default: the baseline '
                         'run\'s final loss)')
    ap.add_argument('--layer-tol-pct', type=float, default=50.0,
                    help='per-layer dynamics drift threshold (%%) for '
                         'the layer-attribution table (default 50)')
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
    base = load_run(args.baseline)
    if not base.scalars:
        print('run_compare: %s banked no scalars records (set '
              'MXTPU_TELEMETRY=1 and MXTPU_SCALARS_EVERY>0)'
              % args.baseline)
        return 2
    rc = 0
    for i, path in enumerate(args.candidates):
        if i:
            print()
        cand = load_run(path)
        if not cand.scalars:
            print('run_compare: %s banked no scalars records' % path)
            rc = max(rc, 2)
            continue
        target = args.target_loss
        if target is None:
            target = base.final_loss()
        if compare_pair(base, cand, tols, target, args.layer_tol_pct):
            rc = max(rc, 1)
    return rc


if __name__ == '__main__':
    sys.exit(main())
