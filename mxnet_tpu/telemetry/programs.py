"""Per-program cost attribution: the compiled-program registrar.

Everything XLA runs for this framework is built at a handful of compile
sites (the executor's fwd / fwd+bwd programs, the fused fit/eval window
programs, the serving bucket ladder). Spans time those dispatches but
the programs themselves would stay anonymous blobs and memory gauges
whole-device totals. This module makes every compiled program
self-describing, following the compiler-stack practice of making
per-program cost a first-class primitive (TVM, arXiv:1802.04799; the
compiled-program boundary as the natural instrumentation unit,
Julia->TPU arXiv:1810.09868):

- :func:`analyze_compiled` — pure: XLA's own ``cost_analysis()`` /
  ``memory_analysis()`` of a compiled executable as a plain dict
  (FLOPs, bytes accessed, temp/argument/output/generated-code bytes).
  Works with telemetry off;
- :func:`note_program` — publish one program's analysis: ``program.*``
  gauges in the registry, a ``program`` JSONL record, a row in the
  end-of-run per-program summary table, and (for programs marked as
  the train step) :func:`telemetry.xla.note_step_flops`, the
  ``xla.step_flops`` gauge;
- :func:`register` — the compile-site interceptor. Wraps a
  ``jax.jit``-ed callable so its lazy compile becomes an explicit
  ``lower().compile()`` whose executable this module can analyze; the
  wrapper then dispatches through the AOT executable (ONE compile
  total, same numerics). With telemetry off it returns the jitted
  callable unchanged — the zero-overhead no-op contract;
- :func:`scope_map` — the one walk of a compiled program's HLO text:
  every instruction that can be a device event (entry, loop-body and
  branch computations; not the insides of fusions) keyed by its name
  to the symbol node or window part whose ``jax.named_scope`` its
  ``op_name`` carries, the pass (``fwd``/``bwd``/``refwd``), the inner
  scope an op planted below the node, and for a fusion how many nodes
  its fused instructions name. With telemetry on it is written once a
  compile to a file beside the telemetry log, named by the ``program``
  record's ``scopes`` key; a reader joins a profiler capture's events
  to it by instruction name (``benchmark/reduce/scopes.py``). The
  ``op_name`` parser (:func:`_layer_from_op_name`) lives here and
  :mod:`.roofline` and :mod:`.memory` import it;
- :func:`maybe_oom_report` — on a ``RESOURCE_EXHAUSTED`` error, dump
  the per-program memory breakdown alongside ``memory_stats()`` so an
  OOM stops being a one-line crash: the report says which programs
  were resident and what XLA planned to allocate for each.
"""
import json
import logging
import os
import re
import threading
import time

__all__ = ['analyze_compiled', 'note_program', 'register',
           'snapshot_programs', 'maybe_oom_report', 'note_nodes',
           'scope_of', 'scope_map', 'WINDOW_PARTS']

_lock = threading.Lock()
_programs = {}          # name -> record dict (see note_program)
_step_flops_seen = {}   # name -> max flops across its recompiles
_oom_reported = False

_ANALYSIS_FIELDS = ('flops', 'bytes_accessed', 'temp_bytes',
                    'argument_bytes', 'output_bytes',
                    'generated_code_bytes', 'alias_bytes', 'live_bytes')


def _state():
    from . import enabled
    enabled()   # decide from the flag if nothing else has yet
    from . import _state as st
    return st


def _empty_analysis():
    return {'flops': 0.0, 'bytes_accessed': 0.0, 'temp_bytes': 0,
            'argument_bytes': 0, 'output_bytes': 0,
            'generated_code_bytes': 0, 'alias_bytes': 0, 'live_bytes': 0}


def analyze_compiled(compiled):
    """XLA's own cost + memory analysis of a compiled executable, as a
    plain dict (zeros where a backend doesn't report). Pure — no
    registry writes, no I/O — so callers that need the numbers with
    telemetry off can use it directly."""
    rec = _empty_analysis()
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        rec['flops'] = float(cost.get('flops', 0.0) or 0.0)
        rec['bytes_accessed'] = float(cost.get('bytes accessed', 0.0) or 0.0)
    except Exception as e:  # noqa: BLE001 — observability must not kill
        logging.debug('telemetry: cost_analysis unavailable: %s', e)
    try:
        ma = compiled.memory_analysis()
        if isinstance(ma, (list, tuple)):
            ma = ma[0]
        for field, attr in (('temp_bytes', 'temp_size_in_bytes'),
                            ('argument_bytes', 'argument_size_in_bytes'),
                            ('output_bytes', 'output_size_in_bytes'),
                            ('generated_code_bytes',
                             'generated_code_size_in_bytes'),
                            ('alias_bytes', 'alias_size_in_bytes')):
            rec[field] = int(getattr(ma, attr, 0) or 0)
        # steady-state footprint of one dispatch: args + temps + outputs
        # minus the donated-input bytes the outputs alias in place. The
        # donation ledger: aliasing a carry moves its output bytes into
        # alias_bytes, so live_bytes is what a window actually makes
        # XLA hold beyond the buffers the caller already owns.
        rec['live_bytes'] = max(0, rec['argument_bytes']
                                + rec['temp_bytes'] + rec['output_bytes']
                                - rec['alias_bytes'])
    except Exception as e:  # noqa: BLE001
        logging.debug('telemetry: memory_analysis unavailable: %s', e)
    return rec


def note_program(name, compiled=None, analysis=None, step_flops=False,
                 compile_s=None):
    """Record one compiled program under ``name``. Returns the analysis
    dict (computed from ``compiled`` when not given) whether or not
    telemetry is on; publication — ``program.*`` gauges, the JSONL
    ``program`` record, the summary-table row, the automatic
    :func:`~.xla.note_step_flops` feed for ``step_flops=True``
    programs — only happens while telemetry is active."""
    if analysis is None:
        analysis = analyze_compiled(compiled) if compiled is not None \
            else _empty_analysis()
    st = _state()
    if not st.active:
        return analysis
    if compiled is not None:
        # roofline attribution (MXTPU_ROOFLINE): parse the program's
        # HLO into per-layer costs while the executable is in hand —
        # one cached-bool check when the flag is off
        from . import roofline
        if roofline.enabled():
            roofline.note_compiled(name, compiled, analysis=analysis,
                                   step_flops=step_flops)
        # memory attribution (MXTPU_MEMORY): same contract — parse the
        # HLO into per-layer buffer bytes while the executable is in
        # hand, one cached-bool check when the flag is off
        from . import memory
        if memory.enabled():
            memory.note_compiled(name, compiled, analysis=analysis)
    with _lock:
        rec = _programs.get(name)
        if rec is None:
            rec = _programs[name] = {'name': name, 'compiles': 0,
                                     'dispatches': 0}
            rec.update(_empty_analysis())
        for f in _ANALYSIS_FIELDS:
            # a name can cover several compiled variants (shape
            # variants, train/eval forms): keep the LARGEST value per
            # field — the conservative bound the OOM report and MFU
            # want, instead of whichever variant compiled last.
            # .get(): hand-crafted analysis dicts (tests, older
            # callers) may predate the alias/live fields
            rec[f] = max(rec[f], analysis.get(f, 0))
        merged = {f: rec[f] for f in _ANALYSIS_FIELDS}
        rec['compiles'] += 1
        compiles = rec['compiles']
    reg = st.registry
    reg.counter('program.compiles').inc()
    # gauges mirror the MERGED record so the two views never disagree
    reg.gauge('program.%s.flops' % name).set(merged['flops'])
    reg.gauge('program.%s.bytes_accessed' % name).set(
        merged['bytes_accessed'])
    reg.gauge('program.%s.temp_bytes' % name).set(merged['temp_bytes'])
    reg.gauge('program.%s.alias_bytes' % name).set(merged['alias_bytes'])
    reg.gauge('program.%s.live_bytes' % name).set(merged['live_bytes'])
    if step_flops and analysis['flops']:
        # the train-step program: its FLOPs are xla.step_flops. XLA
        # counts a scan (while-loop) body ONCE regardless of trip
        # count, so a W-step fused window reports per-step FLOPs
        # already — exactly what note_step_flops wants. Feed the MAX
        # across ALL step-marked programs so far: neither a tail-batch
        # shape variant nor the tail's executor.fwd_bwd (compiled after
        # the fused window, without the update math) may shrink the
        # per-step FLOPs the run reports.
        with _lock:
            _step_flops_seen[name] = max(_step_flops_seen.get(name, 0.0),
                                         analysis['flops'])
            fed = max(_step_flops_seen.values())
        from . import xla
        xla.note_step_flops(fed)
    if st.sink is not None:
        out = {'type': 'program', 'name': name}
        out.update({f: analysis.get(f, 0) for f in _ANALYSIS_FIELDS})
        if compile_s is not None:
            out['compile_s'] = round(float(compile_s), 3)
        if compiled is not None:
            # the instruction-to-scope map, once a compile, in a file of
            # its own: megabytes that the flight recorder's ring (every
            # emitted record passes it) is not to hold
            out.update(_write_scope_map(name, compiled, compiles,
                                        st.sink.path))
        st.sink.emit(out)
    return analysis


def note_dispatch(name):
    """Count one dispatch of a registered program (wrapper-internal)."""
    with _lock:
        rec = _programs.get(name)
        if rec is not None:
            rec['dispatches'] += 1


def snapshot_programs():
    """Point-in-time {name: record} copy — the summary table's input."""
    with _lock:
        return {n: dict(r) for n, r in _programs.items()}


# -- the compile-site interceptor -------------------------------------------

class _RegisteredProgram:
    """AOT wrapper around a jitted callable: the first call per
    argument signature runs ``lower().compile()`` explicitly (one
    compile total — the lazy path would have compiled here anyway),
    hands the executable to :func:`note_program`, then dispatches
    through it. What the ahead-of-time path itself cannot take (the
    TypeError/ValueError of an argument layout, an unhashable leaf)
    falls back to the wrapped lazy jit for that signature —
    attribution is best-effort, execution is not. An error of the
    backend (a compile or a run out of memory) is raised as it is,
    once: the lazy jit would only repeat it."""

    __slots__ = ('name', 'jitted', 'static_argnums', 'step_flops',
                 '_compiled')

    def __init__(self, name, jitted, static_argnums, step_flops):
        self.name = name
        self.jitted = jitted
        self.static_argnums = tuple(static_argnums)
        self.step_flops = step_flops
        self._compiled = {}

    def lower(self, *args, **kwargs):
        return self.jitted.lower(*args, **kwargs)

    def _signature(self, args):
        import jax
        sig = []
        for i, arg in enumerate(args):
            flat, treedef = jax.tree_util.tree_flatten(arg)
            static = i in self.static_argnums
            leaves = []
            for leaf in flat:
                if hasattr(leaf, 'shape') and hasattr(leaf, 'dtype'):
                    leaves.append((tuple(leaf.shape), str(leaf.dtype),
                                   getattr(leaf, 'sharding', None)))
                elif static:
                    # static args select programs by VALUE, exactly as
                    # the jax.jit declaration does
                    leaves.append(('static', leaf))
                else:
                    # a traced python scalar: jit specializes on its
                    # TYPE (weak dtype), never its value — keying by
                    # value would compile per distinct value where the
                    # lazy jit compiles once
                    leaves.append(('scalar', type(leaf)))
            sig.append((treedef, tuple(leaves)))
        return tuple(sig)

    def _compile(self, args, key):
        from . import span
        t0 = time.time()
        try:
            # the two halves of a first call, told apart in the log:
            # tracing and lowering (the program's own Python), then XLA
            with span('program.lower', 'program', program=self.name):
                lowered = self.jitted.lower(*args)
            with span('program.compile', 'program', program=self.name):
                compiled = lowered.compile()
        except (TypeError, ValueError) as e:
            # what the ahead-of-time path itself cannot take (an
            # argument layout): the lazy jit handles it. An error of the
            # backend (RESOURCE_EXHAUSTED among them) is the program's
            # own and reaches the caller as it is, after this one
            # attempt: compiling the same program again through the
            # lazy jit would only fail a second time.
            logging.debug('telemetry: AOT compile of %s failed (%s); '
                          'using lazy jit for this signature',
                          self.name, e)
            self._compiled[key] = False
            return False
        note_program(self.name, compiled=compiled,
                     step_flops=self.step_flops,
                     compile_s=time.time() - t0)
        self._compiled[key] = compiled
        return compiled

    def __call__(self, *args):
        try:
            key = self._signature(args)
            entry = self._compiled.get(key)
        except Exception:  # noqa: BLE001 — unhashable leaf etc.
            return self.jitted(*args)
        if entry is None:
            entry = self._compile(args, key)
        if entry is False:
            return self.jitted(*args)
        if self.static_argnums:
            dyn = [a for i, a in enumerate(args)
                   if i not in self.static_argnums]
        else:
            dyn = args
        try:
            out = entry(*dyn)
        except (TypeError, ValueError) as e:
            # an argument layout/device surprise the signature key
            # missed: the lazy jit handles it (argument checks raise
            # before any buffer is donated, so args are still alive).
            # Runtime errors (a genuine OOM mid-execution) re-raise —
            # retrying after donation would only mask the real failure.
            logging.debug('telemetry: AOT dispatch of %s failed (%s); '
                          'retrying via lazy jit', self.name, e)
            return self.jitted(*args)
        note_dispatch(self.name)
        return out


def register(name, jitted, static_argnums=(), step_flops=False):
    """Intercept a compile site. With telemetry on, returns a wrapper
    that compiles via ``lower().compile()``, analyzes the executable
    (:func:`note_program`), and dispatches through it; with telemetry
    off, returns ``jitted`` unchanged (zero overhead — the hot path
    sees the very same object it constructed).

    ``static_argnums`` must mirror the ``jax.jit`` declaration (AOT
    executables take only the dynamic arguments). ``step_flops=True``
    marks the program whose FLOPs define a training step — it feeds
    the ``xla.step_flops`` gauge."""
    from . import enabled
    if not enabled():
        return jitted
    return _RegisteredProgram(name, jitted, static_argnums, step_flops)


def scope_name(name):
    """Sanitize a symbol/layer name for ``jax.named_scope`` / HLO
    metadata (scopes join with '/', so strip everything exotic)."""
    return re.sub(r'[^A-Za-z0-9_.\-]', '_', str(name)) or '_'


# -- op_name paths: which node, which pass ----------------------------------
#
# Every HLO instruction carries ``metadata={op_name="..."}``: the jax name
# stack at the point the primitive was traced, e.g.
# ``jit(window_fn)/jit(main)/window/while/body/closed_call/
# transpose(jvp(fc1))/dot_general``. The executor runs each symbol node
# under ``jax.named_scope(<node name>)`` and the fused window plants the
# WINDOW_PARTS where no node is; XLA keeps the path of a fusion's root.

# scope segments that are tracing machinery, not layer names. jit()
# segments are FUNCTION boundaries (jit(main), jit(relu)) — dropped
# whole; AD/transform wrappers carry the layer name INSIDE
# (jvp(fc1), transpose(jvp(fc1))) — peeled until the bare name appears
_JIT_RE = re.compile(r'^(jit|pjit)\(')
_XFORM_RE = re.compile(
    r'^(jvp|vjp|transpose|vmap|pmap|xmap|shard_map|remat|'
    r'checkpoint|custom_jvp|custom_vjp|named)\((.*)\)$')
_WRAP_WORDS = frozenset(('while', 'body', 'cond', 'branch', 'scan',
                         'closed_call', 'core_call', 'checkpoint',
                         'rematted_computation', 'custom_vjp_call',
                         'custom_jvp_call', 'custom_lin'))
_BRANCH_RE = re.compile(r'^branch_\d+_fun$')   # lax.cond's, by index

# what the fused window plants where no symbol node is: the optimizer's
# update, the in-window metric plan, the sentinels' stacking, and the
# window's own slicing, learning-rate read and carries
WINDOW_PARTS = ('update', 'metric', 'sentinel', 'window')

# a forward instruction under this segment is computed a second time, in
# the backward pass of a mirrored stage or a ``jax.checkpoint`` (jax
# 0.9: ``.../transpose(jvp(..))/checkpoint/rematted_computation/<node>/
# <primitive>``; held by tests/unittest/test_scope_map.py)
_REMAT_SEG = 'rematted_computation'


def _unwrap_seg(seg):
    """One scope segment -> the layer name it carries, or None.
    ``transpose(jvp(fc1))`` -> ``fc1``; ``jit(relu)`` -> None (a
    function boundary, not a layer); ``while``/``body`` -> None."""
    while True:
        if _JIT_RE.match(seg):
            return None
        m = _XFORM_RE.match(seg)
        if not m:
            break
        seg = m.group(2)
    if not seg or seg in _WRAP_WORDS or _BRANCH_RE.match(seg):
        return None
    return seg


def _layer_from_op_name(op_name):
    """The ``jax.named_scope`` layer in an HLO ``op_name`` path, or
    None. ``jit(f)/jit(main)/fc1/dot_general`` -> ``fc1`` and
    ``jit(f)/while/body/transpose(jvp(fc1))/dot_general`` -> ``fc1``:
    function/loop wrappers are dropped, transform wrappers are peeled,
    the last segment is the primitive, the first before it that is no
    window part is the layer the framework planted (:func:`scope_of`
    with no table of nodes)."""
    sc = scope_of(op_name)
    return sc[0] if sc and sc[1] != '-' else None


_node_ops = {}      # scope name of a symbol node -> its registry op


def note_nodes(table):
    """``{node's scope name: registry op}`` of a symbol the executor is
    about to run: how :func:`scope_map` tells a node's segment from any
    other and names its op. Nothing is kept while telemetry is off."""
    from . import enabled
    if enabled():
        with _lock:
            _node_ops.update(table)


def scope_of(op_name, nodes=None):
    """``(node, phase, inner)`` of one ``op_name`` path, or None where it
    carries no scope the framework planted.

    ``node`` is the first segment that names a symbol node (a key of
    ``nodes``; with no table, the first named segment), else the innermost
    of the WINDOW_PARTS. ``phase``: ``refwd`` where the node lies under
    ``rematted_computation`` (a mirrored stage's or a ``jax.checkpoint``'s
    second forward, with the linearisation jax traces there), ``bwd``
    where a segment up to the node is a ``transpose(..)``, else ``fwd``;
    ``-`` for a window part. ``inner`` is the named segment below the
    node, where an op planted one or a kernel's name stands (XLA joins
    the paths of instructions it merged with ``;``: the first is read)."""
    raw = str(op_name).split(';')[0].split('/')[:-1]    # less the primitive
    named = []                              # (name, bwd so far, remat so far)
    bwd = remat = False
    for seg in raw:
        bwd = bwd or 'transpose(' in seg
        remat = remat or seg == _REMAT_SEG
        u = _unwrap_seg(seg)
        if u is not None:
            named.append((u, bwd, remat))
    part = None
    for i, (u, b, r) in enumerate(named):
        if u in WINDOW_PARTS:
            part = u
        elif nodes is None or u in nodes:
            inner = named[i + 1][0] if i + 1 < len(named) else None
            return u, 'refwd' if r else 'bwd' if b else 'fwd', inner
    return (part, '-', None) if part else None


# opcodes that never run as a device operation of their own
_NO_EVENT = frozenset(('parameter', 'constant', 'tuple',
                       'get-tuple-element', 'bitcast', 'after-all',
                       'partition-id', 'replica-id'))
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLED_RE = re.compile(
    r'\b(calls|body|condition|to_apply|true_computation|'
    r'false_computation)=%?([\w.\-]+)')
_BRANCHES_RE = re.compile(r'branch_computations=\{([^}]*)\}')
_OPERAND_RE = re.compile(r'%([^\s,()]+)')
# instructions whose called computations hold device operations of their
# own (a fusion's, a reduce's or a sort's are part of the instruction)
_HOLDS_EVENTS = frozenset(('while', 'conditional', 'call', 'async-start'))


def _closing(text):
    """Index of the parenthesis that closes the one `text` opens with."""
    depth = 0
    for i, ch in enumerate(text):
        if ch == '(':
            depth += 1
        elif ch == ')':
            depth -= 1
            if depth == 0:
                return i
    return len(text)


def _split_instruction(line):
    """(name, opcode, the text after the opcode) of one HLO instruction
    line, or None."""
    s = line.strip()
    if s.startswith('ROOT '):
        s = s[5:]
    eq = s.find(' = ')
    if eq <= 0 or ' ' in s[:eq]:
        return None
    rest = s[eq + 3:]
    if rest.startswith('('):        # a tuple's type
        rest = rest[_closing(rest) + 1:].lstrip()
    else:
        rest = rest[rest.find(' ') + 1:]
    par = rest.find('(')
    if par <= 0:
        return None
    return s[:eq].lstrip('%'), rest[:par], rest[par:]


def _computations(hlo_text):
    """({computation: [(name, opcode, rest)]}, the entry's name)."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        if cur is None:
            if line.endswith('{') and not line.startswith(' ') \
                    and ('(' in line):
                head = line.split('(', 1)[0].split()
                if head:
                    name = head[-1].lstrip('%')
                    cur = comps.setdefault(name, [])
                    if head[0] == 'ENTRY':
                        entry = name
            continue
        if line.startswith('}'):
            cur = None
            continue
        ins = _split_instruction(line)
        if ins is not None:
            cur.append(ins)
    return comps, entry


def _called(rest):
    names = [m.group(2) for m in _CALLED_RE.finditer(rest)]
    m = _BRANCHES_RE.search(rest)
    if m:
        names += [n.strip().lstrip('%') for n in m.group(1).split(',')
                  if n.strip()]
    return names


def _operands(rest):
    """Names of the instructions that `rest` (an instruction's text from
    its opening parenthesis) takes as operands."""
    return _OPERAND_RE.findall(rest[:_closing(rest)])


def _lend(instrs, order, users, operands):
    """Give each instruction of `order` that has no scope the scope of the
    nearest instruction that uses its result, else of the nearest that
    made an operand of it, through instructions that have none either: a
    copy or a prefetch that XLA put in (it gives those no metadata)
    belongs to what it feeds. Marked ``user`` / ``operand`` in ``via``."""
    for name in order:
        if instrs[name][0] is not None:
            continue
        for via, edges in (('user', users), ('operand', operands)):
            seen, todo, found = {name}, [name], None
            while todo and found is None:
                nxt = []
                for n in todo:
                    for o in edges.get(n, ()):
                        if o in seen or o not in instrs:
                            continue
                        seen.add(o)
                        if instrs[o][0] is not None:
                            found = found or o
                        else:
                            nxt.append(o)
                todo = nxt
            if found is not None:
                instrs[name][:3] = instrs[found][:3]
                instrs[name][5] = via
                break


def scope_map(hlo_text, nodes=None):
    """The walk of a compiled program's HLO text.

    Returns ``{'instrs': {instruction name: [node, phase, inner, opcode,
    fused nodes, via]}, 'nodes': {node: op}, 'named': n, 'unscoped': n}``.
    ``instrs`` holds every instruction of the entry computation and of
    the computations that ``while``, ``conditional`` and ``call``
    instructions run (a device event is named by one of these), with
    :func:`scope_of` of its path (``node`` None where nothing names it;
    for a window part ``node`` is the part and ``phase`` ``-``). For a
    fusion, ``fused nodes`` counts the distinct nodes and parts its fused
    instructions name: 1 is clean, more is a fusion across nodes, which is
    charged to the node of its root (whose path XLA gives it). ``via``
    says where the scope is from when not from the instruction's own path:
    ``inside`` (a fusion whose path names nothing takes the node its
    insides name most), ``user`` or ``operand`` (:func:`_lend`). ``named``
    counts the instructions of ``instrs`` and of their fusions' insides
    that carry an ``op_name``, ``unscoped`` those of them with no node or
    part."""
    if nodes is None:
        with _lock:
            nodes = dict(_node_ops)
    table = nodes or None
    comps, entry = _computations(hlo_text)
    memo = {}       # thousands of instructions share a few hundred paths

    def scope(rest):
        """(whether `rest` carries a path, its scope)."""
        m = _OP_NAME_RE.search(rest)
        if not m:
            return False, None
        path = m.group(1)
        if path not in memo:
            memo[path] = scope_of(path, table)
        return True, memo[path]

    inside = {}     # computation -> ({node: instructions naming it}, named)
    for cname, body in comps.items():
        seen, n = {}, 0
        for _name, opcode, rest in body:
            has_path, sc = scope(rest)
            if not has_path or opcode == 'parameter':
                continue
            n += 1
            if sc is not None:
                seen[sc[0]] = seen.get(sc[0], 0) + 1
        inside[cname] = seen, n
    instrs = {}
    named = unscoped = 0
    todo, done = [entry] if entry else [], set()
    while todo:
        cname = todo.pop()
        if cname in done or cname not in comps:
            continue
        done.add(cname)
        order, users, operands = [], {}, {}
        for name, opcode, rest in comps[cname]:
            if opcode in _HOLDS_EVENTS:
                todo.extend(_called(rest))
            ops = operands[name] = _operands(rest)
            for o in ops:
                users.setdefault(o, []).append(name)
            has_path, sc = scope(rest)
            if opcode not in _NO_EVENT:
                named += has_path
                unscoped += has_path and sc is None
            fused, via = 0, ''
            if opcode == 'fusion':
                seen = {}
                for c in _called(rest):
                    in_c, n = inside.get(c, ({}, 0))
                    named += n
                    unscoped += n - sum(in_c.values())
                    for k, v in in_c.items():
                        seen[k] = seen.get(k, 0) + v
                fused = len(seen)
                if sc is None and seen:
                    top = max(seen, key=lambda k: (seen[k], k))
                    sc = (top, '-' if top in WINDOW_PARTS else 'fwd', None)
                    via = 'inside'
            node, phase, inner = sc or (None, None, None)
            instrs[name] = [node, phase, inner, opcode, fused, via]
            if opcode not in _NO_EVENT:
                order.append(name)
        _lend(instrs, order, users, operands)
    instrs = {n: v for n, v in instrs.items() if v[3] not in _NO_EVENT}
    used = {v[0] for v in instrs.values()}
    return {'instrs': instrs,
            'nodes': {n: op for n, op in (table or {}).items() if n in used},
            'named': named, 'unscoped': unscoped}


def _hlo_text(compiled):
    """The compiled module's text, without the backend configurations (a
    serialized kernel each) and large constants where this jaxlib can
    leave them out."""
    try:
        from jaxlib import _jax
        opts = _jax.HloPrintOptions()
        opts.print_backend_config = False
        opts.print_large_constants = False
        return compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    except Exception:  # noqa: BLE001 — another jaxlib: the whole text
        return compiled.as_text()


def _write_scope_map(name, compiled, n, log_path):
    """Walk `compiled`, the n-th program compiled under `name`, and write
    its map beside the telemetry log. Returns the fields the ``program``
    record names it by, {} on any failure: attribution is best-effort,
    execution is not."""
    t0 = time.time()
    try:
        out = scope_map(_hlo_text(compiled))
        base = '%s.scopes.%s.%d.json' % (
            os.path.splitext(os.path.basename(log_path))[0],
            scope_name(name), n)
        out['program'] = name
        path = os.path.join(os.path.dirname(os.path.abspath(log_path)), base)
        with open(path, 'w') as f:
            json.dump(out, f, separators=(',', ':'))
        return {'scopes': base, 'scopes_s': round(time.time() - t0, 3),
                'scopes_bytes': os.path.getsize(path),
                'scopes_instrs': len(out['instrs']),
                'scopes_unscoped': out['unscoped'],
                'scopes_named': out['named']}
    except Exception as e:  # noqa: BLE001 — observability must not kill
        logging.debug('telemetry: scope map of %s failed: %s', name, e)
        return {}


# -- OOM diagnostics ---------------------------------------------------------

def _looks_like_oom(msg):
    low = msg.lower()
    return 'resource_exhausted' in low or 'resource exhausted' in low


def maybe_oom_report(exc):
    """If ``exc`` is an XLA RESOURCE_EXHAUSTED error (and telemetry is
    on), log the per-program memory breakdown next to the device's
    ``memory_stats()`` and append an ``oom`` JSONL record — once per
    process, so a crash-loop cannot spam the log. Returns True when a
    report was (or already had been) written for an OOM error."""
    st = _state()
    if not st.active:
        return False
    msg = str(exc)
    if not _looks_like_oom(msg):
        return False
    global _oom_reported
    with _lock:
        if _oom_reported:
            return True
        _oom_reported = True
        progs = {n: dict(r) for n, r in _programs.items()}
    from . import xla
    stats = xla.sample_memory()
    lines = ['device OOM (RESOURCE_EXHAUSTED) — per-program memory '
             'breakdown (XLA memory_analysis, bytes XLA planned to '
             'allocate per program):']
    for name in sorted(progs):
        r = progs[name]
        lines.append(
            '  %-44s temp=%8.1f MiB  args=%8.1f MiB  out=%8.1f MiB  '
            'dispatches=%d' % (name, r['temp_bytes'] / 2**20,
                               r['argument_bytes'] / 2**20,
                               r['output_bytes'] / 2**20,
                               r['dispatches']))
    if not progs:
        lines.append('  (no programs registered — the failing compile '
                     'itself may have exhausted memory)')
    if stats:
        keep = ('bytes_in_use', 'peak_bytes_in_use', 'bytes_limit',
                'largest_free_block_bytes')
        lines.append('  device memory_stats: %s' %
                     ', '.join('%s=%s' % (k, stats[k])
                               for k in keep if k in stats))
    else:
        lines.append('  device memory_stats() unavailable on this backend')
    logging.error('%s', '\n'.join(lines))
    if st.sink is not None:
        clean_stats = {k: v for k, v in (stats or {}).items()
                       if isinstance(v, (int, float, str, bool))}
        rec = {'type': 'oom', 'error': msg[:500],
               'programs': progs, 'memory_stats': clean_stats}
        # cross-link what the MXTPU_MEMORY forecaster last said before
        # the allocator died — the post-mortem's "was this predicted?"
        try:
            from . import memory
            fc = memory.last_forecast()
            if fc:
                rec['last_forecast'] = {k: v for k, v in fc.items()
                                        if k != 'type'}
        except Exception:  # noqa: BLE001 — forensics must not add a crash
            pass
        st.sink.emit(rec)
        st.sink.flush()
    # flight recorder: what the process was doing in the records
    # before the allocation failed
    try:
        from . import flight
        flight.dump('oom')
    except Exception:  # noqa: BLE001 — forensics must not add a crash
        pass
    return True


def _reset_for_tests():
    global _oom_reported
    with _lock:
        _programs.clear()
        _step_flops_seen.clear()
        _node_ops.clear()
        _oom_reported = False
