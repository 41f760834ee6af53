"""Device time of a traced slice by symbol node: the capture's leaf seconds
(``run['trace']['by_name']``, keyed by ``"<instruction name> <result type>
<kind>"``) joined to the compiled window's instruction-to-scope map.

The program writes the map once a compile, with telemetry on
(``mxnet_tpu/telemetry/programs.py``: ``scope_map``), into a file beside
its telemetry log; the log's ``program`` record of
``fused_fit.window[...]`` names it under ``scopes``. An entry is
``name -> [node, phase, inner, opcode, fused nodes, via]``: the symbol
node (or window part: ``update``, ``metric``, ``sentinel``, ``window``)
whose ``jax.named_scope`` the instruction's path carries, the pass
(``fwd``, ``bwd``, ``refwd`` for a mirrored stage's second forward, ``-``
for a window part), the scope an op planted below the node or the kernel's
name, how many nodes a fusion's insides name, and whether the scope is the
instruction's own (``''``) or lent by a fusion's insides, a user or an
operand. The map's ``nodes`` gives each node's registry op.

A program without the map (the parent of the PR that brought it) gives
None here, and every reader of it returns None.
"""
import json
import os
import sys

LOOPS = ('while', 'conditional', 'call')


def window_maps(log_path):
    """The maps that the log's ``program`` records of the fused fit window
    name, in the order they were compiled."""
    maps = []
    with open(log_path) as f:
        for line in f:
            if '"scopes"' not in line:
                continue
            rec = json.loads(line)
            if rec.get('type') != 'program' or not str(
                    rec.get('name', '')).startswith('fused_fit.window['):
                continue
            path = os.path.join(os.path.dirname(log_path), rec['scopes'])
            if os.path.exists(path):
                with open(path) as g:
                    maps.append(dict(json.load(g), record=rec))
    return maps


def kernel_of(name, opcode):
    """The program's own name for a kernel call (``pl.pallas_call(name=)``
    names the instruction: ``attention_full_fwd.14``), or None."""
    if opcode != 'custom-call' or name.startswith('custom-call'):
        return None
    return name.rsplit('.', 1)[0]


def join(by_name, scope_map, steps):
    """Seconds a step of `by_name` by what `scope_map` says of each key's
    first token:

    ``rows`` {(op, phase, inner): s} for instructions with a node or part
    (``op`` the node's registry op, or the part);
    ``kernels`` {(op, kernel): s}, the part of ``rows`` in the program's
    named kernel calls;
    ``loops`` {opcode: s}: self time of ``while``, ``conditional`` and
    ``call`` instructions (their bodies' instructions are events of their
    own);
    ``unscoped_s`` in instructions the map holds without a node,
    ``unmapped_s`` in events the map does not hold (another program's),
    ``mixed_s`` the part of ``rows`` in fusions across several nodes
    (charged to their root's), ``lent_s`` the part whose scope came from a
    neighbour, ``total_s`` everything."""
    instrs, nodes = scope_map['instrs'], scope_map.get('nodes', {})
    out = {'rows': {}, 'kernels': {}, 'loops': {}, 'unscoped_s': 0.0,
           'unmapped_s': 0.0, 'mixed_s': 0.0, 'lent_s': 0.0, 'total_s': 0.0,
           'unmapped': {}}
    for key, seconds in by_name.items():
        s = seconds / steps
        out['total_s'] += s
        name = key.split(' ', 1)[0]
        entry = instrs.get(name)
        if entry is None:
            out['unmapped_s'] += s
            out['unmapped'][key] = s
            continue
        node, phase, inner, opcode, fused, via = entry
        if opcode in LOOPS:
            out['loops'][opcode] = out['loops'].get(opcode, 0.0) + s
        elif node is None:
            out['unscoped_s'] += s
        else:
            op = nodes.get(node, node)
            row = (op, phase, inner or '')
            out['rows'][row] = out['rows'].get(row, 0.0) + s
            kernel = kernel_of(name, opcode)
            if kernel:
                k = (op, kernel)
                out['kernels'][k] = out['kernels'].get(k, 0.0) + s
            if fused > 1:
                out['mixed_s'] += s
            if via in ('user', 'operand'):
                out['lent_s'] += s
    return out


def table(run):
    """:func:`join` of a traced run with the map that covers most of its
    device time, cached in ``run['scopes']`` and printed once; None for an
    untraced run or a program that wrote no map."""
    if 'scopes' in run:
        return run['scopes']
    run['scopes'] = None
    trace = run.get('trace') or {}
    log_path = os.environ.get('MXTPU_TELEMETRY_PATH')
    if not trace.get('by_name') or not log_path \
            or not os.path.exists(log_path):
        return None
    steps = max(int(run.get('trace_steps') or 1), 1)
    best = None
    for m in window_maps(log_path):
        t = join(trace['by_name'], m, steps)
        if best is None or t['unmapped_s'] < best['unmapped_s']:
            best = dict(t, program=m.get('program'),
                        record=m.get('record', {}))
    if best is None:
        return None
    best['steps'] = steps
    best['busy_s'] = trace['busy_s'] / steps
    run['scopes'] = best
    print(render(best, run.get('kernels')), file=sys.stderr, flush=True)
    return best


def share(run, rows=None, kernels=None):
    """100 x (seconds of the rows `rows(op, phase, inner)` picks, less
    those of the kernel calls `kernels(op, kernel)` picks) over the busy
    seconds; None without a table."""
    t = table(run)
    if t is None or not t['busy_s']:
        return None
    s = sum(v for k, v in t['rows'].items() if rows(*k))
    if kernels is not None:
        s -= sum(v for k, v in t['kernels'].items() if kernels(*k))
    return 100.0 * s / t['busy_s']


def render(t, run_kernels=None):
    """The table as text: a row an (op, phase), the inner scopes under it,
    then what no row holds and the two checks (the parts against the busy
    time; the attention kernels by scope against the kernels by name)."""
    busy = t['busy_s'] or 1.0
    by_op = {}
    for (op, phase, inner), s in t['rows'].items():
        by_op.setdefault((op, phase), {})[inner] = s
    rec = t.get('record') or {}
    lines = ['[bench scopes] %s: %d steps, %.3f ms busy a step; the map: %s '
             'instructions, %s bytes, walked in %s s'
             % (t.get('program'), t['steps'], 1e3 * t['busy_s'],
                rec.get('scopes_instrs'), rec.get('scopes_bytes'),
                rec.get('scopes_s')),
             '[bench scopes] %-26s %-6s %10s %7s'
             % ('op', 'phase', 'ms/step', '% busy')]
    if not any(op == 'update' for op, _, _ in t['rows']):
        # jax's compile cache keys a program without its metadata: a hit
        # on an entry that another tree made carries that tree's paths
        lines.append('[bench scopes] no instruction under the scope '
                     '\'update\': the executable may be a compile-cache '
                     'entry of a tree that planted other scopes')
    for (op, phase), inner in sorted(by_op.items(),
                                     key=lambda kv: -sum(kv[1].values())):
        s = sum(inner.values())
        lines.append('[bench scopes] %-26s %-6s %10.3f %7.2f'
                     % (op, phase, 1e3 * s, 100 * s / busy))
        if set(inner) != {''}:
            for name, v in sorted(inner.items(), key=lambda kv: -kv[1]):
                lines.append('[bench scopes]   %-31s %10.3f %7.2f'
                             % (name or '(the op itself)', 1e3 * v,
                                100 * v / busy))
    rows_s = sum(t['rows'].values())
    loops_s = sum(t['loops'].values())
    for label, s in (
            ('in rows', rows_s),
            ('  of it in fusions across nodes', t['mixed_s']),
            ('  of it named by a neighbour', t['lent_s']),
            ('unscoped (in the map, no node)', t['unscoped_s']),
            ('unmapped (not in the map)', t['unmapped_s'])) \
            + tuple(('self time of %s' % k, v)
                    for k, v in sorted(t['loops'].items())):
        lines.append('[bench scopes] %-33s %10.3f %7.2f'
                     % (label, 1e3 * s, 100 * s / busy))
    parts = rows_s + t['unscoped_s'] + t['unmapped_s'] + loops_s
    lines.append('[bench scopes] rows + unscoped + unmapped + loops = %.3f '
                 'ms, busy %.3f ms: apart by %.4f%%'
                 % (1e3 * parts, 1e3 * busy, 100 * abs(parts - busy) / busy))
    for key, s in sorted(t['unmapped'].items(), key=lambda kv: -kv[1])[:5]:
        lines.append('[bench scopes]   unmapped: %-40s %8.3f' % (key, 1e3 * s))
    if run_kernels:
        by_name = sum(v for k, v in run_kernels.items()
                      if k.startswith('attention')) / t['steps']
        by_scope = sum(v for (_, kernel), v in t['kernels'].items()
                       if kernel.startswith('attention_'))
        if by_name:
            lines.append('[bench scopes] attention kernels: %.3f ms by scope, '
                         '%.3f by name: apart by %.3f%%'
                         % (1e3 * by_scope, 1e3 * by_name,
                            100 * abs(by_scope - by_name) / by_name))
    return '\n'.join(lines)
