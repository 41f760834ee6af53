"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the compile counter, the comparisons that decide
``correct``, the profiler capture, and the result line.

Nothing here knows a configuration, a traffic mix, a driver or a
per-layer metric by name: each is a file of its own, found by name.
"""
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from statistics import median  # noqa: F401 - the readers' median

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORK = os.path.join(REPO, '.bench_work')


def log(msg, t0=None):
    stamp = '' if t0 is None else '%7.1fs ' % (time.perf_counter() - t0)
    print('[bench %s] %s' % (stamp.strip(), msg), flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_file_module(path, name=None):
    """Import one file by path (its name may hold dots)."""
    name = name or 'bench_' + os.path.basename(path).replace('.', '_')
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def set_path(obj, dotted, value):
    keys = dotted.split('.')
    for k in keys[:-1]:
        obj = obj[k]
    old = obj.get(keys[-1])
    if isinstance(old, bool):
        value = value in ('1', 'true', 'True')
    elif isinstance(old, int):
        value = int(value)
    elif isinstance(old, float):
        value = float(value)
    elif isinstance(old, (list, dict)):
        value = json.loads(value)
    obj[keys[-1]] = value


class Cell:
    """One entry of ``workloads`` with the files it names."""

    def __init__(self, name, overrides=()):
        self.bench = load_json(os.path.join(REPO, 'BENCHMARK.json'))
        cells = {w['name']: w for w in self.bench['workloads']}
        if name not in cells:
            raise SystemExit('no workload %r in BENCHMARK.json (have %s)'
                             % (name, sorted(cells)))
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry['chips'])
        configs = {c['name']: c for c in self.bench['configs']}
        self.config = load_json(
            os.path.join(REPO, configs[self.entry['config']]['file']))
        self.traffic = load_json(os.path.join(
            HERE, 'traffic', self.entry['traffic'] + '.json'))
        # sizes for sweeps and rehearsals only: a measurement sets none
        for item in overrides:
            key, value = item.split('=', 1)
            root, rest = key.split('.', 1)
            set_path({'traffic': self.traffic, 'config': self.config}[root],
                     rest, value)
        self.overridden = bool(overrides)

    def reports(self, metric):
        """Does this cell report `metric` (an entry of end_to_end or
        per_layer)?"""
        if 'workloads' in metric:
            return self.name in metric['workloads']
        if 'moves' in metric:
            moved = next(m for m in self.bench['end_to_end']
                         if m['name'] == metric['moves'])
            return self.reports(moved)
        return True

    def driver(self):
        return load_file_module(os.path.join(
            HERE, 'drivers', self.traffic['driver'] + '.py'))


def build_symbol(config):
    """The configuration's network, from the builder its file names."""
    spec = config['builder']
    path = os.path.join(REPO, spec['file'])
    folder = os.path.dirname(path)
    if folder not in sys.path:      # builders import their siblings
        sys.path.insert(0, folder)
    mod = load_file_module(path)
    return getattr(mod, spec['function'])(**spec.get('kwargs', {}))


def symbol_shapes(sym, batch, image_shape):
    """(parameter names, auxiliary names, {name: shape} of both) of a
    network bound at `batch` images of `image_shape`."""
    args, _, auxs = sym.infer_shape(data=(batch,) + tuple(image_shape),
                                    softmax_label=(batch,))
    shapes = {n: s for n, s in zip(sym.list_arguments(), args)
              if n not in ('data', 'softmax_label')}
    params = list(shapes)
    aux = sym.list_auxiliary_states()
    shapes.update(zip(aux, auxs))
    return params, aux, shapes


class Compiles:
    """Counts XLA compilations of this process (and how many of them the
    persistent cache served) through ``jax.monitoring``."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.endswith('backend_compile_duration'):
            self.compiles += 1
            self.compile_s += float(duration)

    def _event(self, event, **_):
        if event == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1


class Checks:
    """The comparisons that decide ``correct``; each is printed beside its
    limit in every run."""

    def __init__(self):
        self.rows = []

    def at_most(self, name, value, limit):
        ok = bool(value <= limit) and value == value
        self.rows.append((name, value, limit, ok))
        print('[bench check] %-34s %.6g  (limit %.6g)  %s'
              % (name, value, limit, 'ok' if ok else 'FAILED'), flush=True)
        return ok

    def equal(self, name, value, want):
        ok = value == want
        self.rows.append((name, value, want, ok))
        print('[bench check] %-34s %r  (must equal %r)  %s'
              % (name, value, want, 'ok' if ok else 'FAILED'), flush=True)
        return ok

    def true(self, name, ok, note=''):
        ok = bool(ok)
        self.rows.append((name, ok, True, ok))
        print('[bench check] %-34s %s  %s' % (name, note,
                                              'ok' if ok else 'FAILED'),
              flush=True)
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r[3] for r in self.rows)


class Capture:
    """A ``jax.profiler`` capture of a slice of the window, and its
    reduction."""

    def __init__(self, workdir):
        self.dir = os.path.join(workdir, 'trace')
        self.t_start = self.t_stop = None

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self.t_start = time.perf_counter()

    def stop(self):
        import jax
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self, devices, whole_periods_of=0):
        """The slice is what the host's clock says lay between start and
        stop, or, with `whole_periods_of` n, the n - 1 whole periods
        between the starts of the n longest program executions."""
        from benchmark.reduce import trace
        return trace.reduce_file(trace.newest_xplane(self.dir),
                                 self.t_stop - self.t_start, devices,
                                 whole_periods_of)


def make_workdir():
    os.makedirs(WORK, exist_ok=True)
    return tempfile.mkdtemp(prefix='run_', dir=WORK)


def drop_workdir(path):
    shutil.rmtree(path, ignore_errors=True)


def memory_peak(devices):
    """Peak bytes in use on the fullest of `devices` (0 where the backend
    does not say)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get('peak_bytes_in_use', 0)))
    return peak


def percentile(values, q):
    """Nearest-rank percentile of a non-empty list."""
    v = sorted(values)
    k = max(0, min(len(v) - 1, int(-(-q * len(v) // 100)) - 1))
    return v[k]
