#!/usr/bin/env python
"""Diff two roofline records: headroom reclaimed (or lost) per layer.

The roofline block (MXTPU_ROOFLINE=1) names every layer's class and
estimated headroom; this tool closes the loop on an optimization
round by diffing a before/after pair::

    python tools/roofline_diff.py before.jsonl after.jsonl

Each argument is a telemetry JSONL log (the LAST ``roofline`` record
wins, like tools/roofline_report.py).

Layers are matched by name. For each: time delta, headroom delta
(positive ``reclaimed`` = the after-run sits closer to its roofline),
and the class transition when one happened. Ranked by headroom
reclaimed, worst regression last, with step-time and whole-program
totals — the "re-measure" step after pulling a lever. Layers present on only one side are listed (a
renamed scope or a remat-policy flip can legitimately add/remove
layers); ``--json`` dumps the raw diff for scripting.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
TOOLS = os.path.join(REPO, 'tools')
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


def load_roofline(path):
    """The authoritative roofline analysis dict out of one telemetry
    JSONL: its last roofline/summary record."""
    # reuse the report tools' loader conventions
    from telemetry_report import load
    from roofline_report import roofline_records
    recs = roofline_records(load(path))
    if recs:
        return recs[-1][1]
    raise SystemExit(
        'roofline_diff: %s holds no roofline record (need a telemetry '
        'JSONL from MXTPU_ROOFLINE=1)' % path)


def diff(old, new):
    """The layer-matched diff dict of two analysis dicts."""
    o_layers = {r['layer']: r for r in old.get('layers') or []}
    n_layers = {r['layer']: r for r in new.get('layers') or []}
    rows = []
    for layer in sorted(set(o_layers) & set(n_layers)):
        o, n = o_layers[layer], n_layers[layer]
        oh, nh = o.get('headroom_ms'), n.get('headroom_ms')
        rows.append({
            'layer': layer,
            'class_old': o.get('class'), 'class_new': n.get('class'),
            'time_ms_old': o.get('time_ms'),
            'time_ms_new': n.get('time_ms'),
            'headroom_ms_old': oh, 'headroom_ms_new': nh,
            'reclaimed_ms': round(oh - nh, 4)
            if oh is not None and nh is not None else None,
        })
    rows.sort(key=lambda r: -(r['reclaimed_ms'] or 0.0))
    total = round(sum(r['reclaimed_ms'] or 0.0 for r in rows), 4)
    return {
        'program_old': old.get('program'), 'program_new': new.get('program'),
        'source_old': old.get('source'), 'source_new': new.get('source'),
        'step_time_ms_old': old.get('step_time_ms'),
        'step_time_ms_new': new.get('step_time_ms'),
        'layers': rows,
        'only_old': sorted(set(o_layers) - set(n_layers)),
        'only_new': sorted(set(n_layers) - set(o_layers)),
        'total_reclaimed_ms': total,
    }


def _fmt(v):
    if v is None:
        return '-'
    return ('%.4f' % float(v)).rstrip('0').rstrip('.') or '0'


def render(d, old_path, new_path, top=None):
    lines = ['roofline diff: %s -> %s' % (old_path, new_path)]
    if d['source_old'] != d['source_new']:
        lines.append('  note: sources differ (%s vs %s) — modeled and '
                     'measured times are not directly comparable'
                     % (d['source_old'], d['source_new']))
    lines.append('  step_time_ms      %s -> %s'
                 % (_fmt(d['step_time_ms_old']),
                    _fmt(d['step_time_ms_new'])))
    rows = d['layers'][:top] if top else d['layers']
    if rows:
        w = max(max(len(r['layer']) for r in rows), len('layer'))
        lines.append('  %-*s %10s %10s %12s  %s'
                     % (w, 'layer', 'time_old', 'time_new',
                        'reclaimed_ms', 'class'))
        for r in rows:
            cls = r['class_new'] if r['class_new'] == r['class_old'] \
                else '%s -> %s' % (r['class_old'], r['class_new'])
            lines.append('  %-*s %10s %10s %12s  %s'
                         % (w, r['layer'], _fmt(r['time_ms_old']),
                            _fmt(r['time_ms_new']),
                            _fmt(r['reclaimed_ms']), cls))
        if top and len(d['layers']) > top:
            lines.append('  (+%d more layers)' % (len(d['layers']) - top))
    for key, label in (('only_old', 'gone in new'),
                       ('only_new', 'new layers')):
        if d[key]:
            lines.append('  %s: %s' % (label, ', '.join(d[key])))
    lines.append('  total headroom reclaimed: %s ms/step'
                 % _fmt(d['total_reclaimed_ms']))
    return '\n'.join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='Diff two roofline records (telemetry JSONL): '
                    'per-layer headroom reclaimed, class transitions, '
                    'step-time movement.')
    ap.add_argument('old', help='baseline telemetry JSONL')
    ap.add_argument('new', help='candidate telemetry JSONL')
    ap.add_argument('--top', type=int, default=16,
                    help='rows rendered (default 16; 0 = all)')
    ap.add_argument('--json', action='store_true',
                    help='dump the raw diff dict as JSON instead')
    args = ap.parse_args(argv)
    d = diff(load_roofline(args.old), load_roofline(args.new))
    if args.json:
        print(json.dumps(d, indent=2))
        return 0
    print(render(d, args.old, args.new, top=args.top or None))
    return 0


if __name__ == '__main__':
    sys.exit(main())
