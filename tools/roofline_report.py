#!/usr/bin/env python
"""Render the roofline attribution block from a telemetry JSONL log,
offline.

A run with ``MXTPU_TELEMETRY=1 MXTPU_ROOFLINE=1`` appends a
``roofline`` record (and folds the same dict into the ``summary``
record) carrying the per-layer achieved-vs-peak analysis. This tool
re-renders it without re-running anything::

    python tools/roofline_report.py telemetry.jsonl

Uses the SAME renderer as the live end-of-run summary
(mxnet_tpu/telemetry/export.py::_roofline_lines), so the offline block
is byte-identical to the one the run logged — the round-trip the
roofline tests pin. ``--json`` dumps the raw analysis dict instead
(for scripting: jq over layers/classes/headroom). Multiple records
(several write_summary calls, or several runs appending to one
log) keep the LAST one — the end-of-run view — unless ``--all`` lists
every one with its timestamp.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from mxnet_tpu.telemetry.export import _roofline_lines  # noqa: E402
from telemetry_report import load  # noqa: E402  (same loader conventions)


def roofline_records(records):
    """Every roofline analysis dict in a parsed record list, oldest
    first: the dedicated ``roofline`` records, plus any ``summary``
    record's ``roofline`` key (a crashed run may have either)."""
    out = []
    for r in records:
        if r.get('type') == 'roofline':
            out.append((r.get('t'), {k: v for k, v in r.items()
                                     if k not in ('type', 't', 'host')}))
        elif r.get('type') == 'summary' and r.get('roofline'):
            out.append((r.get('t'), r['roofline']))
    return out


def render(roof):
    """One analysis dict -> the summary-table block, as a string."""
    return '\n'.join(_roofline_lines(roof))


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='Render the roofline attribution block (per-layer '
                    'compute-/memory-/overhead-bound classification, '
                    'achieved/peak %, headroom, collective accounting) '
                    'from a telemetry JSONL log, offline — byte-identical '
                    'to the block the live summary table logged.')
    ap.add_argument('path', help='telemetry JSONL file to render')
    ap.add_argument('--json', action='store_true',
                    help='dump the raw analysis dict(s) as JSON instead '
                         'of the rendered block')
    ap.add_argument('--all', action='store_true',
                    help='render every roofline record in the log, not '
                         'just the last')
    args = ap.parse_args(argv)
    recs = roofline_records(load(args.path))
    if not recs:
        sys.stderr.write(
            'roofline_report: %s holds no roofline record — was the run '
            'started with MXTPU_TELEMETRY=1 MXTPU_ROOFLINE=1?\n'
            % args.path)
        return 1
    picked = recs if args.all else recs[-1:]
    if args.json:
        dicts = [r for _t, r in picked]
        print(json.dumps(dicts[0] if len(dicts) == 1 else dicts,
                         indent=2))
        return 0
    blocks = []
    for t, roof in picked:
        if args.all and t is not None:
            blocks.append('== t=%s ==' % t)
        blocks.append(render(roof))
    print('\n'.join(blocks))
    return 0


if __name__ == '__main__':
    sys.exit(main())
