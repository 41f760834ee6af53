"""Typed, validated configuration: env-flag catalog + parameter structs.

Reference: dmlc-core's parameter.h (`DMLC_DECLARE_FIELD` with defaults,
ranges and enums, `Init(kwargs)` validation with readable errors) and
docs/how_to/env_var.md (the catalog of `MXNET_*` environment variables).

Two pieces:

- ``Flag`` / ``flags``: every environment variable the framework reads,
  declared centrally with type, default, and doc. ``flags.get(name)``
  parses + validates once and caches; ``flags.describe()`` prints the
  catalog (the env_var.md equivalent). Reference ``MXNET_*`` spellings
  are accepted as aliases for the ``MXTPU_*`` names.
- ``Parameter``/``field``: a small dmlc-Parameter analog for validated
  option structs (ranges, enums, required fields) used by iterators and
  tools.
"""
import os
import threading

__all__ = ['Flag', 'FlagRegistry', 'flags', 'Parameter', 'field']


class Flag:
    def __init__(self, name, type_, default, doc, aliases=(), choices=None,
                 min_value=None, max_value=None):
        self.name = name
        self.type = type_
        self.default = default
        self.doc = doc
        self.aliases = tuple(aliases)
        self.choices = choices
        self.min_value = min_value
        self.max_value = max_value

    def parse(self, raw):
        if raw is None:
            return self.default
        try:
            if self.type is bool:
                val = raw.strip().lower() not in ('', '0', 'false', 'no')
            else:
                val = self.type(raw)
        except (TypeError, ValueError):
            raise ValueError(
                'env %s=%r: expected %s' % (self.name, raw,
                                            self.type.__name__))
        if self.choices is not None and val not in self.choices:
            raise ValueError('env %s=%r: must be one of %s'
                             % (self.name, raw, sorted(self.choices)))
        if self.min_value is not None and val < self.min_value:
            raise ValueError('env %s=%r: must be >= %s'
                             % (self.name, raw, self.min_value))
        if self.max_value is not None and val > self.max_value:
            raise ValueError('env %s=%r: must be <= %s'
                             % (self.name, raw, self.max_value))
        return val


class FlagRegistry:
    def __init__(self):
        self._flags = {}
        self._cache = {}
        self._lock = threading.Lock()

    def declare(self, name, type_, default, doc, **kwargs):
        flag = Flag(name, type_, default, doc, **kwargs)
        self._flags[name] = flag
        return flag

    def get(self, name):
        """Parsed + validated value of a declared flag (cached; reference
        dmlc::GetEnv but with the declaration enforced)."""
        with self._lock:
            if name in self._cache:
                return self._cache[name]
            flag = self._flags[name]  # KeyError = undeclared flag: a bug
            raw = os.environ.get(flag.name)
            if raw is None:
                for alias in flag.aliases:
                    raw = os.environ.get(alias)
                    if raw is not None:
                        break
            val = flag.parse(raw)
            self._cache[name] = val
            return val

    def reload(self, name=None):
        """Drop cached values (tests mutate os.environ)."""
        with self._lock:
            if name is None:
                self._cache.clear()
            else:
                self._cache.pop(name, None)

    def describe(self):
        """The env_var.md catalog as text."""
        lines = []
        for name in sorted(self._flags):
            f = self._flags[name]
            alias = (' (alias: %s)' % ', '.join(f.aliases)) if f.aliases else ''
            lines.append('%s [%s, default %r]%s\n    %s'
                         % (name, f.type.__name__, f.default, alias, f.doc))
        return '\n'.join(lines)

    def __iter__(self):
        return iter(self._flags.values())


flags = FlagRegistry()

# ---- the catalog (reference: docs/how_to/env_var.md) ----------------------
flags.declare('MXTPU_ENGINE_WORKERS', int, 4,
              'Worker threads in the native dependency engine',
              aliases=('MXNET_CPU_WORKER_NTHREADS',), min_value=1,
              max_value=512)
flags.declare('MXTPU_ENGINE_TYPE', str, 'ThreadedEngine',
              'Engine scheduling mode; NaiveEngine = synchronous debugging '
              'mode (race detection off the table by construction)',
              aliases=('MXNET_ENGINE_TYPE',),
              choices={'NaiveEngine', 'ThreadedEngine',
                       'ThreadedEnginePerDevice'})
flags.declare('MXTPU_NO_NATIVE', bool, False,
              'Skip loading/building the native runtime library '
              '(pure-python fallbacks for engine/recordio/profiler)')
flags.declare('MXTPU_BACKWARD_DO_MIRROR', str, '0',
              "Gradient-memory tradeoff: '1' (or any truthy value) = "
              "rematerialization of the forward under jax.checkpoint, "
              "all but the values an op named as dear to recompute "
              "(what an attention kernel's backward pass reads, a "
              "contracting FullyConnected's output, an expert layer's "
              "routing and plan, a gated MLP's two hidden products), "
              "'dots' = keep matmul results (checkpoint_dots policy), "
              "'0'/''/'false' = off (legacy spellings honored)",
              aliases=('MXNET_BACKWARD_DO_MIRROR',))
flags.declare('MXTPU_CONV_BWD_PATCHES', bool, False,
              'compute conv2d weight gradients as an explicit im2col '
              'patches-matmul instead of conv_backprop_filter '
              '(groups=1 2D convs only)')
flags.declare('MXTPU_CONV_STEM_S2D', bool, False,
              'rewrite thin-input strided convs (cin<=4, stride>1: the '
              'image-network stem) into space-to-depth + stride-1 convs; '
              'exact reparametrization that the MXU tiles far better than '
              'a cin=3 strided conv')
flags.declare('MXTPU_TELEMETRY', bool, False,
              'Runtime telemetry (mxnet_tpu/telemetry): span/counter/'
              'gauge registry over the train hot path, XLA compile and '
              'memory gauges, JSONL metrics log + end-of-run summary '
              'table. Off = zero-overhead no-op path')
flags.declare('MXTPU_TELEMETRY_PATH', str, 'telemetry.jsonl',
              'Append-only JSONL metrics log written while '
              'MXTPU_TELEMETRY=1 (one JSON record per line: spans, '
              'compile events, end-of-run summary)')
flags.declare('MXTPU_TELEMETRY_RETRACE_WARN', int, 5,
              'Warn (once, loudly) when the same graph is compiled more '
              'than this many times — the retrace-storm detector',
              min_value=1)
flags.declare('MXTPU_TELEMETRY_PORT', int, -1,
              'Live telemetry endpoint (telemetry/serve.py, requires '
              'MXTPU_TELEMETRY=1): serve /metrics (Prometheus text), '
              '/healthz (200/503 from the health incident state) and '
              '/summary (registry snapshot as JSON) from a stdlib HTTP '
              'server on a daemon thread. 0 binds an OS-assigned '
              'ephemeral port; -1 (default) = off: no thread, no socket',
              min_value=-1, max_value=65535)
flags.declare('MXTPU_TELEMETRY_SYNC_EVERY', int, 0,
              'Cluster telemetry sync cadence (telemetry/cluster.py, '
              'requires MXTPU_TELEMETRY=1): every N training steps run '
              'one small off-graph allgather carrying each host\'s key '
              'gauges (step-time p50, io-wait share, dispatch span, '
              'live bytes); process 0 publishes cluster.* per-host '
              'gauges, spread, slowest-host id and the straggler '
              'classification. 0 (default) = off: the fit loops never '
              'touch the hook', min_value=0)
flags.declare('MXTPU_TELEMETRY_MAX_MB', float, 0.0,
              'Size cap (MB) for the JSONL telemetry log: once the file '
              'would exceed it, records are dropped (counted under '
              'telemetry.dropped_records, warned once) instead of '
              'filling the disk on week-long runs. 0 = unlimited',
              min_value=0.0)
flags.declare('MXTPU_GOODPUT', bool, True,
              'Goodput accounting plane (telemetry/goodput.py, requires '
              'MXTPU_TELEMETRY=1 — telemetry off means true no-op): '
              'classify every second of measured wall-clock into named '
              'buckets (productive step compute, XLA compile, input '
              'wait, checkpoint, eval, collective comm, restart rework, '
              'unattributed overhead) from the existing span/mark '
              'sites; buckets + overhead sum to wall-clock exactly. '
              'goodput.* gauges, a goodput JSONL record, the "Where the '
              'time went" summary block, /metrics + /summary, fleet '
              'aggregation through the cluster sync vector. 0 = off')
flags.declare('MXTPU_GOODPUT_LOST_S', float, 0.0,
              'Cumulative lost-work seconds of PRIOR supervised '
              'attempts, stamped into a relaunched child\'s environment '
              'by tools/train_supervisor.py / tools/gang_supervisor.py '
              '(dead-attempt wall since the last_good checkpoint '
              'pointer). The goodput record reports it as prior_lost_s '
              'with the derived job_wall_s / job_goodput_pct; per-'
              'process buckets still sum to per-process wall. Not for '
              'humans to set', min_value=0.0)
flags.declare('MXTPU_TIMELINE', bool, False,
              'Pod-level step timeline (telemetry/timeline.py, requires '
              'MXTPU_TELEMETRY=1): cross-host clock alignment piggy-'
              'backed on the cluster sync allgather (no new collective; '
              'cluster.h<i>.clock_offset_ms), a step-phase ledger from '
              'the existing spans, and per-sync-round critical-path '
              'attribution — the gang step decomposed into compute / '
              'collective-wait / io / host-side with the gating host '
              'AND phase named (timeline.critical_host/critical_phase/'
              'skew_ms gauges, timeline JSONL records, the "step '
              'timeline" summary block; tools/trace_merge.py stitches '
              'the per-host logs into one offset-corrected Perfetto '
              'trace). Off (default) = true no-op: one cached-bool per '
              'seam, lowered programs byte-identical, the sync vector '
              'slots ride as NaN')
flags.declare('MXTPU_TELEMETRY_BIND', str, '127.0.0.1',
              'Bind address for the live telemetry endpoint '
              '(telemetry/serve.py). Default 127.0.0.1 = loopback only; '
              "set to '0.0.0.0' (or empty) to expose /metrics /healthz "
              '/summary on all interfaces — do that only behind scrape-'
              'infra access control (docs/observability.md)')
flags.declare('MXTPU_CKPT_DIR', str, '',
              'Root directory for periodic sharded training checkpoints '
              '(module/checkpointing.py over parallel/checkpoint.py\'s '
              'orbax tier): each host writes only its own shards, so '
              'save/restore cost scales with per-host bytes, not model '
              'size. Must be a path every host of a multi-host job can '
              'reach. Empty (default) = checkpointing off')
flags.declare('MXTPU_CKPT_EVERY', int, 0,
              'Save a training checkpoint every N trained steps '
              '(quantized to window boundaries on the fused-fit path). '
              'Captures params, optimizer state, RNG streams, epoch/'
              'step cursor and eval-metric state; saves are '
              'asynchronous — the step loop is never blocked on the '
              'write. 0 (default) = off (MXTPU_CKPT_DIR must also be '
              'set)', min_value=0)
flags.declare('MXTPU_CKPT_KEEP', int, 3,
              'How many checkpoint steps to retain (orbax max_to_keep '
              'pruning); older steps are deleted as new ones commit',
              min_value=1)
flags.declare('MXTPU_CKPT_ASYNC', bool, True,
              'Write checkpoints on a background thread (the step loop '
              'only captures array references and moves on). If the '
              'async writer dies, checkpointing falls back to '
              'synchronous saves — and if those fail too, training '
              'continues without checkpoints (warn, never crash). 0 '
              'forces synchronous saves from the start')
flags.declare('MXTPU_CKPT_RESUME', bool, True,
              'At fit() start, restore from the newest health-certified '
              'checkpoint (the last-good pointer) when MXTPU_CKPT_DIR '
              'holds one: parameters, optimizer state, RNG streams and '
              'the epoch/step cursor come back bit-exactly and the '
              'data iterator is skipped to the restored step. 0 always '
              'starts fresh (existing checkpoints are left alone)')
flags.declare('MXTPU_RESTART_MAX', int, 3,
              'Restart budget for the supervised training driver '
              '(module/resilient_fit.py, tools/train_supervisor.py): '
              'how many times a failed run is restored from last-good '
              'and resumed before the failure is re-raised', min_value=0)
flags.declare('MXTPU_RESTART_BACKOFF', float, 2.0,
              'Base backoff (seconds) between supervised restarts; '
              'attempt k waits backoff * 2^(k-1), capped at 60s',
              min_value=0.0)
flags.declare('MXTPU_FAULT_INJECT', str, '',
              'Deterministic fault injection (mxnet_tpu/faults.py): '
              "'<kind>:<step>[:<arg>]' with kind one of nan-grad, "
              'checkpoint-corrupt, dispatch-exception, '
              'slow-host, hang, host-loss, '
              'mem-hog, clock-skew — fires one real fault '
              'at a deterministic training step so every recovery path '
              '(health raise, restore-from-last-good, restart backoff) '
              'is exercised by real tests, not mocks. '
              'Empty (default) = off: every seam is one cached-bool '
              'check and the compiled programs are untouched')
flags.declare('MXTPU_HEALTH', bool, False,
              'Training-health sentinels (telemetry/health, requires '
              'MXTPU_TELEMETRY=1): in-graph NaN/Inf detection with '
              'exact-step attribution through the fused windows, a '
              'first-bad-layer bisect, rolling-baseline spike detectors '
              'over step time / loss / grad-norm, and a "Run health" '
              'block in the telemetry summary. Off (or telemetry off) = '
              'true no-op: the compiled programs are byte-identical')
flags.declare('MXTPU_HEALTH_ACTION', str, 'warn',
              "What a non-finite incident does: 'warn' logs it (rate-"
              "limited), 'record' only appends the health JSONL record, "
              "'raise' raises telemetry.health.TrainingHealthError with "
              'the diagnostic (step, window step, first bad layer) '
              'attached. Spike anomalies never raise',
              choices={'warn', 'record', 'raise'})
flags.declare('MXTPU_HEALTH_K', float, 8.0,
              'Spike threshold for the health anomaly detectors: an '
              'observation more than K robust deviations (MAD) from '
              'the rolling median is an anomaly', min_value=1.0)
flags.declare('MXTPU_HEALTH_WINDOW', int, 64,
              'Trailing-window length (observations) backing the health '
              "anomaly detectors' rolling median/MAD baseline",
              min_value=4)
flags.declare('MXTPU_TFEVENTS_DIR', str, '',
              'Directory for native TensorBoard event files '
              '(telemetry/ledger.py): every ledger scalar '
              '(MXTPU_SCALARS_EVERY) is also encoded as a tfevents '
              'record through the dependency-free TFRecord/Event '
              'writer — `tensorboard --logdir <dir>` works on any run '
              'without tensorboardX or torch installed. Empty '
              '(default) = no event file is written')
flags.declare('MXTPU_WATCHDOG_SECS', float, 0.0,
              'Hang watchdog (telemetry/watchdog.py): once the training '
              'loop has made its first progress mark, a daemon thread '
              'checks that marks (per-batch/per-window dispatch, eval '
              'windows, cluster sync rounds, kvstore push/pull, '
              'checkpoint commits) keep arriving at least this often. '
              'On a stall it dumps all-thread stacks + the last '
              'telemetry state as a hang JSONL incident, flips /healthz '
              'to 503 with a hung digest, and applies '
              'MXTPU_WATCHDOG_ACTION. Set it above the worst legitimate '
              'gap (an XLA recompile can take 20-40s). 0 (default) = '
              'off: no thread is ever created', min_value=0.0)
flags.declare('MXTPU_WATCHDOG_ACTION', str, 'warn',
              "What the hang watchdog does on a stall: 'warn' records "
              "the incident and keeps waiting (clears when progress "
              "resumes), 'abort' additionally exits the process with "
              'the distinct code 85 so tools/train_supervisor.py '
              'relaunches from the last-good checkpoint',
              choices={'warn', 'abort'})
flags.declare('MXTPU_SUPERVISOR_LIVENESS', float, 0.0,
              'Supervisor-side liveness tier (tools/train_supervisor.py, '
              'read from the environment — the supervisor never imports '
              'the framework): if the child process appends no new '
              'bytes to its MXTPU_TELEMETRY_PATH JSONL for this many '
              'seconds, the supervisor SIGTERMs (then SIGKILLs) and '
              'relaunches it against the same restart budget — the '
              'tier for a child too wedged to run its own in-process '
              'watchdog. Needs the child run with MXTPU_TELEMETRY=1; '
              'set it well above MXTPU_WATCHDOG_SECS so the in-process '
              'watchdog acts first. 0 (default) = off', min_value=0.0)
flags.declare('MXTPU_ELASTIC_INPUT', bool, False,
              'Straggler-aware input re-balancing (telemetry/cluster.py, '
              'requires MXTPU_TELEMETRY=1 and '
              'MXTPU_TELEMETRY_SYNC_EVERY>0): when a cluster sync round '
              'classifies the slowest host as input-bound, every host '
              'deterministically computes the same shifted shard '
              'assignment from the same gathered round and applies it '
              'at the next epoch boundary via the iterator '
              'shard_info()/set_shard() protocol (ImageRecordIter, '
              'MNISTIter). Off (default) = the fit loops never touch '
              'the hook')
flags.declare('MXTPU_KVSTORE_TIMEOUT', float, 0.0,
              'Bound (seconds) on each kvstore_dist push/pull server '
              'reply. A shard request that exceeds it counts as a '
              'transient connection error and enters the '
              'MXTPU_KVSTORE_RETRIES reconnect-and-retry path instead '
              'of hanging into the watchdog. 0 (default) = unbounded '
              '(the pre-retry behavior)', min_value=0.0)
flags.declare('MXTPU_KVSTORE_RETRIES', int, 2,
              'How many times a kvstore_dist push/pull shard request is '
              'retried after a transient connection error (socket '
              'error, or an MXTPU_KVSTORE_TIMEOUT expiry): each retry '
              'reconnects to the server and backs off exponentially '
              '(0.05s * 2^k, capped at 2s). Past the budget the error '
              're-raises as ConnectionError — retryable by '
              'resilient_fit/the supervisor. 0 = a single attempt',
              min_value=0)
flags.declare('MXTPU_XPROF', str, '',
              "One-shot step-windowed device-trace capture: 'start:stop' "
              "(training-step counts) arms jax.profiler to start once "
              "`start` steps have completed and stop at `stop`, writing "
              'a TensorBoard/Perfetto trace to MXTPU_XPROF_DIR. The '
              'fused fit path advances a whole window of steps per '
              'device call, so boundaries quantize to window multiples '
              'there. MXTPU_PROFILER_XLA_TRACE=0 disables it. Empty = off')
flags.declare('MXTPU_XPROF_DIR', str, 'xprof_trace',
              'Output directory for the MXTPU_XPROF device trace')
flags.declare('MXTPU_ROOFLINE', bool, False,
              'Roofline attribution (mxnet_tpu/telemetry/roofline.py, '
              'requires MXTPU_TELEMETRY=1): parse every registered '
              "program's HLO into per-layer FLOPs/bytes by "
              'jax.named_scope layer name, distribute the measured step '
              'time by roofline-minimum times (modeled), classify each '
              'layer compute-/memory-/overhead-bound against the chip '
              'peak table, and account collective bytes and modeled '
              'time per step. Off = no HLO text is ever rendered or parsed (one '
              'cached-bool check at the program registrar)')
flags.declare('MXTPU_MEMORY', bool, False,
              'HBM attribution & forecast plane '
              '(mxnet_tpu/telemetry/memory.py, requires '
              'MXTPU_TELEMETRY=1): attribute every registered '
              "program's argument/temp/output/alias bytes to named "
              'layers (HLO buffer parse calibrated against '
              "XLA's own memory_analysis totals), keep a bounded "
              'live-bytes ring sampled at the scalars cadence, and '
              'forecast steps-to-OOM — a forecast at or below '
              'MXTPU_MEMORY_OOM_STEPS flips /healthz to mem_pressure '
              'and dumps the flight recorder BEFORE the allocator '
              'dies. Off = no HLO text is ever rendered or parsed and '
              'no ring is filled (one cached-bool check at the '
              'registrar and the step loops)')
flags.declare('MXTPU_MEMORY_OOM_STEPS', int, 200,
              'mem_pressure threshold for the MXTPU_MEMORY forecaster: '
              'a linear steps-to-OOM forecast at or below this many '
              'steps trips the alarm (healthz mem_pressure + the '
              'flight-mem-pressure dump). Forecasts above it only '
              'publish the mem.steps_to_oom gauge', min_value=1)
flags.declare('MXTPU_PEAK_TFLOPS', float, 0.0,
              'Override the device peak dense bf16 TFLOP/s used by the '
              'roofline denominators (for chips '
              'missing from the telemetry/xla.py table — the '
              'warn-once path names this flag). 0 = use the table',
              min_value=0.0)
flags.declare('MXTPU_PEAK_HBM_GBS', float, 0.0,
              'Override the device peak HBM GB/s used by the roofline '
              'denominators (pairs with MXTPU_PEAK_TFLOPS). 0 = use '
              'the table', min_value=0.0)
flags.declare('MXTPU_PROFILER_XLA_TRACE', bool, True,
              'Attach jax.profiler (the device trace) alongside the '
              'host-span trace when the profiler runs, and allow the '
              'MXTPU_XPROF capture; 0 keeps the host spans only')
flags.declare('MXTPU_FORCE_PALLAS', bool, False,
              'Dispatch LayerNorm/softmax/attention to the Pallas kernels '
              'even off-TPU (interpret mode; exercises the kernel path on '
              'the CPU test mesh)')
flags.declare('MXTPU_KVSTORE_BIGARRAY_BOUND', int, 1 << 20,
              'Arrays with >= this many elements are striped across all '
              'servers on push/pull',
              aliases=('MXNET_KVSTORE_BIGARRAY_BOUND',), min_value=1)
flags.declare('MXTPU_KVSTORE_DEBUG', bool, False,
              'Verbose logging in the distributed kvstore tier')
flags.declare('MXTPU_NO_SPMD_MODULE', bool, False,
              'Disable the fused single-program (GSPMD) lowering for '
              'multi-context Module; fall back to the per-device loop')
flags.declare('MXTPU_FUSED_FIT', bool, True,
              'Allow Module.fit to compile a window of N train steps '
              'into one XLA call (lax.scan) when eligible '
              '(module/fused_fit.py); 0 forces the per-batch loop')
flags.declare('MXTPU_FIT_STEPS_PER_CALL', int, 0,
              'Window size for the fused Module.fit fast path; 0 = '
              'auto (32 on TPU, 4 elsewhere)', min_value=0)
flags.declare('MXTPU_FUSED_EVAL', bool, True,
              'Allow score/predict/iter_predict to compile a window of '
              'N forward steps into one XLA call (lax.scan) with '
              'on-device metric accumulation or a stacked-output '
              'fetch — one dispatch + one fetch per window instead of '
              'two per batch (module/fused_eval.py); 0 forces the '
              'per-batch loop')
flags.declare('MXTPU_EVAL_STEPS_PER_CALL', int, 0,
              'Window size for the fused eval/inference fast path; '
              '0 = auto (32 on TPU, 4 elsewhere)', min_value=0)
flags.declare('MXTPU_FUSED_EVAL_PREFETCH', bool, True,
              'Pipeline the fused-eval window input: window k+1\'s '
              'host-stack + host->device transfer run on a side '
              'thread while window k computes on device; 0 restores '
              'the serial stack/put/dispatch/fetch order')
flags.declare('MXTPU_SHARDED_UPDATE', bool, True,
              'ZeRO-style sharded weight update in the SPMD fused-fit '
              'window (arXiv:2004.13336): grads reduce-scatter, each '
              'replica updates 1/dp of EVERY param (leaves flattened '
              'and zero-padded to a multiple of dp), weights '
              'all-gather — optimizer state + master params live '
              'dp-sharded between windows, so their per-device bytes '
              'drop ~dp x (update.opt_state_bytes_per_device gauge). '
              'Engages only with an SPMD dp mesh (dp > 1) and the '
              'module not opted out (module.sharded_update = False); '
              'anywhere else the update runs replicated (warn-once '
              'when the flag was set explicitly). 0 keeps the '
              'replicated update everywhere')
flags.declare('MXTPU_GRAD_COMPRESS', str, 'off',
              'Quantized gradient collectives with error feedback '
              '(parallel/compression.py, EQuARX recipe): int8 = '
              'block-quantized grads with per-block scales and a '
              'persistent error-feedback residual carried through the '
              'fused window; bf16 = half-width cast, no scales; auto = '
              'start uncompressed, flip to int8 when a cluster sync '
              'round classifies the run communication_bound (the flip '
              'rebuilds the window program and emits one compression '
              'JSONL record with the step-time delta). Also switches '
              'the kvstore_dist push/pull wire format to compressed, '
              'version-tagged payloads. off lowers byte-identically '
              'to the uncompressed program. Gauges: comm.bytes_on_'
              'wire_per_step, comm.compression_ratio',
              choices={'off', 'int8', 'bf16', 'auto'})
flags.declare('MXTPU_GRAD_COMPRESS_BLOCK', int, 256,
              'Block size for int8 gradient quantization: one fp32 '
              'scale (amax/127) per this many gradient elements. '
              'Smaller blocks track outliers tighter at more scale '
              'overhead (4 bytes per block on the wire)',
              min_value=8)
flags.declare('MXTPU_BN_ONEPASS', bool, True,
              'BatchNorm training stats via one-pass moments '
              '(sum/sum-of-squares in one fused HBM read of the '
              'activation) instead of jnp.var\'s two-pass mean-then-'
              'centered-square. Default ON; no chip run on record '
              'supports the default (ROADMAP S9, D10: one paired '
              'measurement on the benchmark decides it). 0 is the '
              'two-pass jnp.var form; numerics are parity-tested both '
              'ways '
              '(tests/unittest/test_bn_onepass.py)')
flags.declare('MXTPU_FUSED_DONATE', bool, True,
              'Donate the fused-fit window\'s inputs to XLA: the '
              'param/optimizer/aux carry (aliased onto the matching '
              'outputs — the weight update runs in place) AND the '
              'stacked input window + per-step label stacks (freed '
              'by the runtime at their last in-program use instead '
              'of surviving until the next window rebinds them, so '
              'two windows\' stacks never need to be live at once '
              'under the prefetch pipeline). program.<window>.'
              'live_bytes / alias_bytes in the registrar carry the '
              'before/after evidence. 0 disables ALL window '
              'donation — the undonated reference program the '
              'donation-safety parity tests compare against')
flags.declare('MXTPU_REMAT_POLICY', str, '',
              "Rematerialization policy for the fused-fit window "
              "body, the roofline block's memory-bound lever: "
              "'none' = save every forward residual (explicitly "
              "overrides MXTPU_BACKWARD_DO_MIRROR for the window), "
              "'dots' = keep matmul/conv results and recompute the "
              "rest (jax checkpoint_dots policy), 'full' = "
              "rematerialize the whole forward in backward (max "
              "temp-memory saving, ~1/3 more FLOPs). Empty (default) "
              "defers to MXTPU_BACKWARD_DO_MIRROR exactly as before. "
              "Flipping it between fit() calls rebuilds the window",
              choices={'', 'none', 'dots', 'full'})
flags.declare('MXTPU_HOST_CROP', bool, True,
              'In ImageRecordIter device-augment mode, workers crop '
              '(rand or center) to the target HxW before handover, so '
              'the uploaded uint8 window carries H*W/S^2 of the source '
              'bytes (23% fewer for 224^2 crops of 256^2 sources); '
              'mirror + normalize stay on device. 0 ships the full '
              'fixed-size source and crops on device')
flags.declare('MXTPU_FUSED_FIT_PREFETCH', bool, True,
              'Pipeline the fused-fit window input: window k+1\'s '
              'host-stack + host->device transfer run on a side '
              'thread while window k computes on device (np.stack '
              'and the transfer release the GIL, so the overlap holds '
              'even on a one-core host). 0 restores the serial '
              'stack/put/dispatch/fetch order')
flags.declare('MXTPU_DEVICE_AUGMENT', bool, False,
              'ImageRecordIter ships fixed-size uint8 batches and runs '
              'crop/mirror/normalize as one jitted device call per '
              'batch (io/image_record.py device-augment mode) — for '
              'few-core hosts that cannot feed the chip from the '
              'host-side augment path')
flags.declare('MXTPU_F16_AS_BF16', bool, False,
              'Resolve float16 dtype requests to bfloat16, the TPU '
              'native half type (the MXU has no fp16 datapath)')
flags.declare('MXTPU_EXEC_BULK_EXEC_MAX_NODE_TRAIN', int, 15,
              'Max ops bulked into one engine push by the executor',
              aliases=('MXNET_EXEC_BULK_EXEC_MAX_NODE_TRAIN',), min_value=1)
flags.declare('MXTPU_PROFILER_AUTOSTART', bool, False,
              'Start the profiler at init (reference '
              'MXNET_PROFILER_AUTOSTART)',
              aliases=('MXNET_PROFILER_AUTOSTART',))
flags.declare('MXTPU_COORDINATOR', str, '',
              'host:port of the jax.distributed coordinator for the '
              'multi-host SPMD tier (set by tools/launch.py; the DCN '
              'analog of DMLC_PS_ROOT_URI/PORT)')
flags.declare('MXTPU_NUM_HOSTS', int, 1,
              'Process count of the multi-host SPMD job '
              '(DMLC_NUM_WORKER analog)', min_value=1)
flags.declare('MXTPU_HOST_ID', int, 0,
              'This process\'s rank in the multi-host SPMD job',
              min_value=0)
flags.declare('MXTPU_COORD_TIMEOUT', float, 0.0,
              'Bound (seconds) on each attempt to join the '
              'jax.distributed job in parallel/multihost.init_multihost '
              '(passed as initialization_timeout). 0 (default) = jax\'s '
              'own default (5 minutes). tools/gang_supervisor.py '
              'defaults its workers to 60 (an explicit setting wins) '
              'so workers orphaned by a dead coordinator fail fast and '
              'the gang can be torn down and relaunched on a fresh '
              'port', min_value=0.0)
flags.declare('MXTPU_FAULT_HOST', int, -1,
              'Restrict the MXTPU_FAULT_INJECT fault to ONE host of a '
              'multi-process job: the fault arms only in the process '
              'whose MXTPU_HOST_ID matches (the launcher env reaches '
              'every worker of a gang, and a chaos test usually wants '
              'to lose exactly one). -1 (default) = arm wherever the '
              'env reaches', min_value=-1)
flags.declare('MXTPU_SCALARS_EVERY', int, 25,
              'Run-ledger scalar cadence (telemetry/ledger.py, requires '
              'MXTPU_TELEMETRY=1): every N trained steps one `scalars` '
              'JSONL record banks the step\'s loss, learning rate, '
              'throughput and global + worst-layer gradient statistics '
              '— the bounded per-step timeseries tools/'
              'run_compare.py diffs across runs — and the per-layer '
              'dynamics plane (MXTPU_DYNAMICS) publishes its gauges at '
              'the same decimated cadence. With MXTPU_TFEVENTS_DIR set '
              'each record also lands as native TensorBoard scalars. '
              '0 = no scalar records (the manifest still writes)',
              min_value=0)
flags.declare('MXTPU_SERVE_BIND', str, '127.0.0.1',
              'Bind address for the model-serving HTTP frontend '
              '(mxnet_tpu/serving/http.py, tools/serve_model.py). '
              'Default 127.0.0.1 = loopback only; set to \'0.0.0.0\' '
              '(or empty) to serve on all interfaces — do that only '
              'behind a load balancer / access control '
              '(docs/serving.md)')
flags.declare('MXTPU_SERVE_MAX_BATCH', int, 32,
              'Largest serving batch bucket (mxnet_tpu/serving): the '
              'engine pre-compiles one forward program per power-of-'
              'two bucket up to this size, and the dynamic batcher '
              'coalesces queued requests up to the largest bucket per '
              'dispatch. Steady-state serving then never recompiles '
              '(every request pads to a warm bucket)',
              min_value=1, max_value=65536)
flags.declare('MXTPU_SERVE_MAX_WAIT_MS', float, 5.0,
              'Longest time (milliseconds) the serving batcher holds '
              'the oldest queued request while coalescing more '
              'arrivals into one padded dispatch. A dispatch fires as '
              'soon as the largest warm bucket is full OR this '
              'deadline expires, whichever comes first — the knob '
              'trades tail latency for batch efficiency '
              '(docs/serving.md). 0 dispatches each poll immediately',
              min_value=0.0)
flags.declare('MXTPU_SERVE_SESSIONS', int, 64,
              'Session capacity of the autoregressive serving step '
              'cache (mxnet_tpu/serving/step_cache.py): per-session '
              'carried state (RNN/LSTM hidden state) lives in a '
              'device-resident ring of this many slots, evicted LRU. '
              'A decode step then dispatches ONE fixed-shape program '
              'per token batch instead of re-running the prefix '
              '(arXiv:2603.09555\'s O(1) autoregressive caching)',
              min_value=1)
flags.declare('MXTPU_FLIGHT_RECORDER', int, 2048,
              'Incident flight recorder (telemetry/flight.py, requires '
              'MXTPU_TELEMETRY=1): a fixed-size in-memory ring retaining '
              'the last N telemetry records (spans, traces, health/'
              'anomaly events) at negligible cost — no extra I/O, no '
              'thread. Every incident path (watchdog stall, non-finite '
              'incident, OOM report, SLO burn, supervised restart) dumps '
              'the ring to a flight-<reason>.jsonl next to the telemetry '
              'log, so a postmortem has the seconds BEFORE the incident '
              'without full telemetry export. Render with '
              'tools/trace_report.py. 0 = off: no ring is ever allocated',
              min_value=0, max_value=1 << 20)
flags.declare('MXTPU_SLO_LATENCY_MS', float, 0.0,
              'Serving latency objective (telemetry/slo.py, requires '
              'MXTPU_TELEMETRY=1): a request slower than this many '
              'milliseconds counts against the error budget exactly '
              'like a server-side error. Together with '
              'MXTPU_SLO_ERROR_PCT it arms the SLO plane: slo.* gauges '
              'on /metrics (burn rate, budget remaining) and an '
              '"slo_degraded" /healthz state on sustained burn — '
              'distinct from "hung" and the non-finite "degraded". '
              '0 (default) = no latency objective', min_value=0.0)
flags.declare('MXTPU_SLO_ERROR_PCT', float, 0.0,
              'Serving error budget (telemetry/slo.py): the allowed '
              'share (%) of bad requests — server-side 5xx errors plus '
              'requests over MXTPU_SLO_LATENCY_MS. The rolling burn '
              'rate is bad_share/budget; burn >= 1 sustained over the '
              'MXTPU_SLO_WINDOW flips /healthz to slo_degraded (and '
              'back when the window clears). 0 (default) = no error '
              'objective; with only the latency objective set the '
              'budget defaults to 1%', min_value=0.0, max_value=100.0)
flags.declare('MXTPU_SLO_WINDOW', int, 128,
              'Rolling request window (count) backing the SLO burn-rate '
              'computation (telemetry/slo.py): burn and the degraded '
              'verdict are computed over the most recent this-many '
              'requests, so recovery is automatic once fresh traffic '
              'meets the objectives', min_value=8)
flags.declare('MXTPU_DYNAMICS', bool, False,
              'Per-layer training dynamics (telemetry/dynamics.py, '
              'requires MXTPU_TELEMETRY=1): extend the in-graph health '
              'sentinel from one global vector to a per-parameter '
              'matrix — per-layer gradient norm, parameter norm and '
              'update ratio ||dw||/||w||, plus an activation '
              'zero-fraction per named graph output (dead-ReLU '
              'detection) — computed inside the already-compiled '
              'fused-fit window and per-batch executor programs and '
              'shipped home in the window\'s EXISTING single fetch (no '
              'added device syncs). Publishes dynamics.<layer>.* '
              'gauges + `dynamics` JSONL records at the '
              'MXTPU_SCALARS_EVERY cadence and feeds each layer\'s '
              'grad-norm/update-ratio into the MXTPU_HEALTH spike '
              'detectors so a vanishing or exploding LAYER raises a '
              'named anomaly before the global norm moves. Off (or '
              'telemetry off) = true no-op: the compiled programs are '
              'byte-identical ("Following training dynamics", '
              'docs/observability.md)')
flags.declare('MXTPU_GANG_MIN_HOSTS', int, 0,
              'Elastic floor for tools/gang_supervisor.py (read from '
              'the environment — the supervisor never imports the '
              'framework; --elastic-min-hosts overrides): when a gang '
              'relaunch is triggered by a host-loss exit (code 113) '
              'and more than this many workers remain, the gang '
              'relaunches with one fewer worker instead of the full '
              'set — reshard-on-restore + io.auto_shard re-derive '
              'shard coverage from the smaller process set. 0 '
              '(default) = never shrink: relaunches always use the '
              'full worker count', min_value=0)


# The one default place of the persistent compilation cache: a fixed path
# inside the checkout (listed in .gitignore). The path is part of the
# cache key, so it never holds a temp dir, a pid or a time. It assumes the
# package is run from a source checkout, as everything in this repo is;
# installed elsewhere, name the directory with JAX_COMPILATION_CACHE_DIR.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    '.jax_compile_cache')


def enable_compile_cache():
    """Decide where jax's persistent XLA compilation cache lives; the one
    function that does. Called at package import, before the process's
    first compile, and touches no device. Returns the directory in use,
    or None when the cache is off.

    - ``JAX_COMPILATION_CACHE_DIR`` set: jax already uses that directory;
      no other is set in code.
    - not set, platform list pinned to ``cpu`` (the tests' CPU mesh,
      context.cpu_mesh_mode): off.
    - not set, otherwise (a process that may use the chip): on, at
      :data:`COMPILE_CACHE_DIR`; off with a warning where that directory
      cannot be made (a package installed outside a writable checkout).

    Every executable is cached, not only the slow-to-compile ones;
    telemetry counts served compiles under ``xla.cache_hits``."""
    import jax
    from .context import cpu_mesh_mode
    path = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if not path:
        if cpu_mesh_mode():
            return None
        path = COMPILE_CACHE_DIR
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as e:
            import logging
            logging.warning('compile cache off: %s cannot be made (%s); '
                            'set JAX_COMPILATION_CACHE_DIR', path, e)
            return None
        jax.config.update('jax_compilation_cache_dir', path)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    return path


# ---- dmlc::Parameter analog ----------------------------------------------

class _Field:
    __slots__ = ('name', 'type', 'default', 'required', 'min_value',
                 'max_value', 'choices', 'doc')

    def __init__(self, type_, default=None, required=False, min_value=None,
                 max_value=None, choices=None, doc=''):
        self.name = None  # set by ParameterMeta
        self.type = type_
        self.default = default
        self.required = required
        self.min_value = min_value
        self.max_value = max_value
        self.choices = choices
        self.doc = doc

    def check(self, value, owner):
        if value is None:
            if self.required:
                raise ValueError('%s: required parameter %r missing'
                                 % (owner, self.name))
            return self.default
        if self.type is bool and isinstance(value, str):
            value = value.strip().lower() not in ('', '0', 'false', 'no')
        elif not isinstance(value, self.type):
            try:
                value = self.type(value)
            except (TypeError, ValueError):
                raise ValueError('%s.%s=%r: expected %s'
                                 % (owner, self.name, value,
                                    self.type.__name__))
        if self.choices is not None and value not in self.choices:
            raise ValueError('%s.%s=%r: must be one of %s'
                             % (owner, self.name, value,
                                sorted(self.choices)))
        if self.min_value is not None and value < self.min_value:
            raise ValueError('%s.%s=%r: must be >= %s'
                             % (owner, self.name, value, self.min_value))
        if self.max_value is not None and value > self.max_value:
            raise ValueError('%s.%s=%r: must be <= %s'
                             % (owner, self.name, value, self.max_value))
        return value


def field(type_, default=None, **kwargs):
    """Declare a validated field on a Parameter subclass
    (DMLC_DECLARE_FIELD)."""
    return _Field(type_, default, **kwargs)


class ParameterMeta(type):
    def __new__(mcls, name, bases, ns):
        fields = {}
        for base in bases:
            fields.update(getattr(base, '_fields', {}))
        for key, val in list(ns.items()):
            if isinstance(val, _Field):
                val.name = key
                fields[key] = val
                del ns[key]
        ns['_fields'] = fields
        return super().__new__(mcls, name, bases, ns)


class Parameter(metaclass=ParameterMeta):
    """Validated option struct (dmlc::Parameter::Init).

    >>> class ConvParam(Parameter):
    ...     kernel = field(tuple, required=True)
    ...     num_filter = field(int, required=True, min_value=1)
    ...     layout = field(str, 'NCHW', choices={'NCHW', 'NHWC'})
    >>> p = ConvParam(kernel=(3, 3), num_filter=8)
    """

    def __init__(self, **kwargs):
        cls = type(self).__name__
        unknown = set(kwargs) - set(self._fields)
        if unknown:
            raise ValueError('%s: unknown parameter(s) %s'
                             % (cls, sorted(unknown)))
        for name, f in self._fields.items():
            setattr(self, name, f.check(kwargs.get(name), cls))

    def asdict(self):
        return {name: getattr(self, name) for name in self._fields}

    def __repr__(self):
        return '%s(%s)' % (type(self).__name__,
                           ', '.join('%s=%r' % kv
                                     for kv in sorted(self.asdict().items())))
