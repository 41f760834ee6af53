"""BaseModule — the canonical train loop.

Reference: python/mxnet/module/base_module.py:376 (fit: bind → init_params →
init_optimizer → per-batch forward_backward/update/metric/callbacks),
score/predict/forward_backward and the parameter-access contract.
"""
import logging
import time
import warnings

import numpy as np

from .. import faults as _faults
from .. import metric as metric_mod
from .. import ndarray as nd
from .. import profiler as _profiler
from .. import telemetry as _tele
from ..io import DataDesc
from ..model import BatchEndParam
from ..initializer import Uniform

__all__ = ['BaseModule']


def _check_input_names(symbol, names, typename, throw):
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [arg for arg in args if not arg.endswith('_weight') and
                      not arg.endswith('_bias') and not arg.endswith('_gamma')
                      and not arg.endswith('_beta')]
        msg = "\033[91mYou created Module with Module(..., %s_names=%s) but " \
              "input with name '%s' is not found in symbol.list_arguments(). " \
              "Did you mean one of:\n\t%s\033[0m" % (
                  typename, str(names), name, '\n\t'.join(candidates))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


def _check_names_match(data_names, data_shapes, name, throw):
    """Reference base_module.py:56 — input descriptor names must match
    the module's declared names: mismatched data names raise; label
    mismatches only warn (predict-time modules bind without labels).
    Without this gate a wrong label_name surfaces much later as a
    KeyError in the executor group (or trains silently through the
    fused window's positional binding)."""
    actual = [x[0] for x in data_shapes]
    if sorted(data_names) != sorted(actual):
        msg = "Data provided by %s_shapes don't match names specified by " \
              "%s_names (%s vs. %s)" % (name, name, str(data_shapes),
                                        str(data_names))
        if throw:
            raise ValueError(msg)
        warnings.warn(msg)


def _parse_data_desc(data_names, label_names, data_shapes, label_shapes):
    data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                   for x in data_shapes]
    _check_names_match(data_names, data_shapes, 'data', True)
    if label_shapes is not None:
        label_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                        for x in label_shapes]
        _check_names_match(label_names, label_shapes, 'label', False)
    else:
        _check_names_match(label_names, [], 'label', False)
    return data_shapes, label_shapes


class BaseModule:
    """Reference base_module.py:66."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high-level API ---------------------------------------------------
    def forward_backward(self, data_batch):
        """Reference base_module.py:189."""
        self.forward(data_batch, is_train=True)
        self.backward()

    def _set_eval_rate(self, nbatches, batch_size, tic):
        """eval_samples_per_sec gauge, the eval twin of the fit loop's
        speedometer.samples_per_sec (no-op while telemetry is off)."""
        if nbatches and batch_size:
            dt = time.time() - tic
            if dt > 0:
                _tele.gauge('eval_samples_per_sec').set(
                    round(nbatches * batch_size / dt, 2))

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Reference base_module.py:204."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        tic = time.time()

        # TPU fast path: compile a window of N forward steps + on-device
        # metric accumulation into one XLA call (lax.scan) when the
        # module/metric combination allows it — one dispatch and one
        # fetch per window instead of two per batch (module/
        # fused_eval.py). Falls back silently, like fit's fused window.
        from .fused_eval import FusedEvalLoop
        fused = FusedEvalLoop.build_cached(self, eval_metric,
                                           logger=self.logger)
        if fused is not None:
            actual_num_batch = fused.run_score(eval_data, eval_metric,
                                               num_batch,
                                               batch_end_callback, epoch)
        else:
            actual_num_batch = 0
            for nbatch, eval_batch in enumerate(eval_data):
                if num_batch is not None and nbatch == num_batch:
                    break
                with _tele.span('eval.dispatch', 'eval'):
                    self.forward(eval_batch, is_train=False)
                with _tele.span('eval.metric', 'eval'):
                    self.update_metric(eval_metric, eval_batch.label)
                _tele.counter('eval.batches').inc()
                _tele.watchdog.note_progress('eval.step')
                if batch_end_callback is not None:
                    params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                           eval_metric=eval_metric,
                                           locals=locals())
                    for callback in _as_list(batch_end_callback):
                        callback(params)
                actual_num_batch += 1
        self._set_eval_rate(actual_num_batch,
                            getattr(eval_data, 'batch_size', 0), tic)
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        # the eval loop's progress marks armed the hang watchdog; this
        # driven region is over — disarm so a standalone score followed
        # by long host work cannot false-trip (inside fit the next
        # epoch's first step mark re-arms immediately)
        _tele.watchdog.suspend()
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        try:
            # fused window path (one dispatch + one fetch per N
            # batches); silent fallback to the per-batch loop
            from .fused_eval import FusedEvalLoop
            fused = FusedEvalLoop.build_cached(self, None,
                                               logger=self.logger)
            if fused is not None:
                yield from fused.iter_windows(eval_data, num_batch)
                return
            for nbatch, eval_batch in enumerate(eval_data):
                if num_batch is not None and nbatch == num_batch:
                    break
                with _tele.span('eval.dispatch', 'eval'):
                    self.forward(eval_batch, is_train=False)
                pad = eval_batch.pad
                with _tele.span('eval.fetch', 'eval'):
                    outputs = [out[0:out.shape[0] - pad]
                               for out in self.get_outputs()]
                _tele.counter('eval.batches').inc()
                yield (outputs, nbatch, eval_batch)
        finally:
            # fused windows marked the hang watchdog: disarm when the
            # consumer stops (exhaustion OR early generator close)
            _tele.watchdog.suspend()

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Reference base_module.py:292."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        tic = time.time()
        from .fused_eval import FusedEvalLoop
        fused = FusedEvalLoop.build_cached(self, None, logger=self.logger)
        output_list = []
        if fused is not None:
            # windowed forward: outputs arrive per batch already
            # pad-trimmed and host-resident (one fetch per window)
            for outputs, _, _ in fused.iter_windows(eval_data, num_batch):
                output_list.append(outputs)
        else:
            for nbatch, eval_batch in enumerate(eval_data):
                if num_batch is not None and nbatch == num_batch:
                    break
                with _tele.span('eval.dispatch', 'eval'):
                    self.forward(eval_batch, is_train=False)
                pad = eval_batch.pad
                with _tele.span('eval.fetch', 'eval'):
                    outputs = [out[0:out.shape[0] - pad].copy()
                               for out in self.get_outputs()]
                _tele.counter('eval.batches').inc()
                output_list.append(outputs)
        self._set_eval_rate(len(output_list),
                            getattr(eval_data, 'batch_size', 0), tic)
        # same disarm as score(): predict's windows marked the watchdog
        _tele.watchdog.suspend()
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, \
                    'Cannot merge batches, as num of outputs is not the same ' \
                    'in mini-batches. Maybe bucketing is used?'
            output_list2 = [nd.concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric='acc',
            epoch_end_callback=None, batch_end_callback=None, kvstore='local',
            optimizer='sgd', optimizer_params=(('learning_rate', 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None):
        """THE canonical train loop (reference base_module.py:376)."""
        assert num_epoch is not None, 'please specify number of epochs'

        # decide telemetry before bind: the XLA compile listener must be
        # live before this fit's first compile so warmups are counted
        _tele.enabled()
        # the set-up spans (with fused_fit.build): where a job's time to
        # its first step goes, as a tree in the log
        with _tele.span('fit.bind', 'fit'):
            self.bind(data_shapes=train_data.provide_data,
                      label_shapes=train_data.provide_label,
                      for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        with _tele.span('fit.init_params', 'fit'):
            self.init_params(initializer=initializer,
                             arg_params=arg_params, aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init)
        with _tele.span('fit.init_optimizer', 'fit'):
            self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                                optimizer_params=optimizer_params)

        # the rest of the set-up, up to the first draw: the metric, the
        # checkpointer's restore, the fused loop with its optimizer states
        # (float32 masters and momentum are made here), the planes' flags
        with _tele.span('fit.prepare_loop', 'fit'):
            if validation_metric is None:
                validation_metric = eval_metric
            if not isinstance(eval_metric, metric_mod.EvalMetric):
                eval_metric = metric_mod.create(eval_metric)

            # resilience tier (module/checkpointing.py): periodic async
            # sharded checkpoints + restore-from-last-good, built from the
            # MXTPU_CKPT_* flags. Restore happens HERE — before the fused
            # window programs are built — so a resumed run binds the same
            # programs a fresh one would. Flags off = None, nothing runs.
            from .checkpointing import TrainCheckpointer
            ckpt = TrainCheckpointer.for_fit(self, eval_metric,
                                             logger=self.logger)
            # fault-injection harness (mxnet_tpu/faults.py): one cached
            # bool; every seam below is dead code while the flag is unset
            faults_on = _faults.enabled()

            # TPU fast path: compile a window of N steps into one XLA call
            # (lax.scan) when the module/optimizer/metric combination allows
            # it — same numerics, one dispatch per window instead of four
            # per batch (see module/fused_fit.py). Falls back silently.
            fused = None
            if monitor is None:
                from .fused_fit import FusedFitLoop
                fused = FusedFitLoop.build_cached(self, eval_metric,
                                                  logger=self.logger)
            if fused is None:
                # flag honesty: an explicitly-requested MXTPU_SHARDED_UPDATE
                # can only engage inside the fused SPMD window — the
                # per-batch reference loop below updates replicated
                from .fused_fit import (_shard_update_requested,
                                        note_replicated_update)
                if _shard_update_requested():
                    note_replicated_update(
                        'the per-batch reference loop is running '
                        '(no fused window built)', site='fit')
            # training-health sentinels (telemetry/health): the per-batch
            # loop feeds the step-time spike detector; the in-graph
            # finite/norm sentinels ride the executor's fwd+bwd program.
            # One cached-bool check — zero overhead while off. The cluster
            # sync hook (telemetry/cluster.py) is gated the same way.
            health_on = _tele.health.enabled()
            # per-layer dynamics (telemetry/dynamics): executor-level rows
            # take their step index from the same note_batch context the
            # health incidents use, so the batch context is fed when EITHER
            # plane is on
            dyn_on = _tele.dynamics.enabled()
            cluster_on = _tele.cluster.enabled()
            # run ledger (telemetry/ledger): every fit() emits a fresh
            # run_seq-tagged manifest — a second in-process fit (or a
            # resilient_fit retry) may run under different flags, and
            # run_compare keys on the latest; the per-step scalars
            # (loss/lr/throughput/grad stats) bank at MXTPU_SCALARS_EVERY
            ledger_on = _tele.ledger.enabled()
            _tele.ledger.begin_run(module=self)
            # hang watchdog (telemetry/watchdog.py): per-step progress marks
            # feed the stall monitor; off = one cached-bool check here and
            # no call in the loop
            wd_on = _tele.watchdog.enabled()
            # live-bytes timeline (telemetry/memory): one cached-bool check
            # here, a host-side allocator sample at the scalars cadence
            mem_on = _tele.memory.enabled()
            # pod step timeline (telemetry/timeline): the per-step counter
            # behind the phase ledger's per-step normalization — the phase
            # durations themselves ride the spans this loop already emits
            tl_on = _tele.timeline.enabled()

        try:
            for epoch in range(begin_epoch, num_epoch):
                tic = time.time()
                eval_metric.reset()
                if ckpt is not None and not ckpt.begin_epoch(
                        epoch, eval_metric, train_data):
                    # resume fast-forward: this epoch was fully trained
                    # before the restore point — skip it without touching
                    # the data or running its eval
                    continue
                if fused is not None:
                    nbatch = fused.run_epoch(train_data, eval_metric, epoch,
                                             batch_end_callback, ckpt=ckpt)
                    self._fit_epoch_end(epoch, eval_metric, tic,
                                        epoch_end_callback, eval_data,
                                        validation_metric, eval_end_callback,
                                        eval_batch_end_callback)
                    if cluster_on:
                        # elastic input re-balancing: a pending shard
                        # shift applies here, before the reset re-draws
                        _tele.cluster.apply_shard_shift(train_data,
                                                        logger=self.logger)
                    train_data.reset()
                    continue
                # a resumed epoch's first batch IS batch r_step: true
                # batch-in-epoch indices for callbacks and incidents
                nbatch = ckpt.epoch_nbatch_base if ckpt is not None else 0
                data_iter = iter(train_data)
                end_of_batch = False
                next_data_batch = None
                try:
                    next_data_batch = next(data_iter)
                except StopIteration:
                    if ckpt is None or not ckpt.allow_empty_epoch(epoch):
                        raise
                    # the restore point was exactly this epoch's boundary:
                    # the resume skip consumed every batch, so the epoch
                    # is already trained — fall through to its epoch end
                    end_of_batch = True
                while not end_of_batch:
                    data_batch = next_data_batch
                    if faults_on:
                        # nan-grad draw seam (batches counted in step order)
                        data_batch = _faults.maybe_poison_batch(data_batch)
                    if monitor is not None:
                        monitor.tic()
                    t_step = time.time() if health_on else 0.0
                    if health_on or dyn_on:
                        # executor-level incidents carry the real batch index
                        _tele.health.note_batch(nbatch)
                    # per-batch telemetry: host-dispatch vs draw vs metric vs
                    # callback time (all no-ops unless MXTPU_TELEMETRY=1 or
                    # the chrome-trace profiler is running)
                    with _tele.span('fit.batch', 'fit'):
                        with _tele.span('fit.dispatch', 'fit'):
                            self.forward_backward(data_batch)
                            self.update()
                        _tele.counter('fit.steps').inc()
                        if wd_on:
                            _tele.watchdog.note_progress('fit.step')
                        # MXTPU_XPROF step-windowed device-trace capture
                        _profiler.note_step()
                        try:
                            with _tele.span('fit.draw', 'fit'):
                                next_data_batch = next(data_iter)
                            self.prepare(next_data_batch)
                        except StopIteration:
                            end_of_batch = True
                        with _tele.span('fit.metric', 'fit'):
                            self.update_metric(eval_metric, data_batch.label)
                        if monitor is not None:
                            monitor.toc_print()
                        if batch_end_callback is not None:
                            batch_end_params = BatchEndParam(
                                epoch=epoch, nbatch=nbatch,
                                eval_metric=eval_metric, locals=locals())
                            with _tele.span('fit.callback', 'fit'):
                                for callback in _as_list(batch_end_callback):
                                    callback(batch_end_params)
                    if health_on:
                        _tele.health.note_step_time(time.time() - t_step)
                    if cluster_on:
                        # off-sync steps: one clock read + a deque append;
                        # the allgather fires every SYNC_EVERY steps only
                        _tele.cluster.note_step()
                    if ledger_on:
                        # lr passed lazily: the scheduler sample only
                        # runs on the decimated due steps
                        _tele.ledger.note_train_step(
                            lr=lambda: _cur_lr(
                                getattr(self, '_optimizer', None)),
                            metric=eval_metric)
                    if ckpt is not None:
                        # per-batch path: the sentinel check already ran in
                        # backward, so health trails by nothing (lag=0)
                        ckpt.note_steps(1)
                    if faults_on:
                        _faults.note_steps(1)
                    if mem_on:
                        _tele.memory.note_step(1)
                    if tl_on:
                        _tele.timeline.note_step(1)
                    nbatch += 1

                self._fit_epoch_end(epoch, eval_metric, tic,
                                    epoch_end_callback, eval_data,
                                    validation_metric, eval_end_callback,
                                    eval_batch_end_callback)
                if cluster_on:
                    _tele.cluster.apply_shard_shift(train_data,
                                                    logger=self.logger)
                train_data.reset()
        except BaseException as e:  # noqa: BLE001 — incl. Ctrl-C/exit
            if ckpt is not None:
                # the run is dying with a save possibly in flight: drain
                # and certify NOW, while the interpreter is whole — at
                # teardown orbax's commit thread loses its executors
                # ("cannot schedule new futures after shutdown") and the
                # save would never commit, leaving a supervised relaunch
                # (tools/train_supervisor.py) nothing to restore. A
                # KeyboardInterrupt drains too: preserving the last save
                # is exactly what an interrupted operator wants.
                # Idempotent: resilient_fit's handle_failure call after
                # this re-raise finds nothing pending.
                diag = getattr(e, 'diagnostic', None)
                try:
                    ckpt.handle_failure(dict(diag) if diag else None)
                except Exception:  # noqa: BLE001 — never mask the failure
                    pass
            if wd_on:
                # fit is over (however it ended): stop expecting marks
                # so post-training host work cannot false-trip
                _tele.watchdog.suspend()
            raise

        if ckpt is not None:
            # final save + writer drain + last-good certification
            # (its commit emits one more progress mark — suspend after)
            ckpt.finish()
        if wd_on:
            _tele.watchdog.suspend()

    def _fit_epoch_end(self, epoch, eval_metric, tic, epoch_end_callback,
                       eval_data, validation_metric, eval_end_callback,
                       eval_batch_end_callback):
        """Epoch-end bookkeeping shared by the reference per-batch loop
        and the fused fast path (reference base_module.py:528-553)."""
        # the batch loop is over: clear the executor-incident step
        # context so a later custom-loop incident cannot inherit a
        # stale index (one attribute store — safe while health is off)
        _tele.health.note_batch(None)
        _tele.counter('fit.epochs').inc()
        _tele.xla.sample_memory()   # live/peak device bytes, once per epoch
        name_vals = eval_metric.get_name_value()
        for name, val in name_vals:
            self.logger.info('Epoch[%d] Train-%s=%f', epoch, name, val)
        _tele.ledger.note_eval([('train-%s' % n, v) for n, v in name_vals],
                               epoch=epoch)
        toc = time.time()
        self.logger.info('Epoch[%d] Time cost=%.3f', epoch, (toc - tic))

        arg_params_, aux_params_ = self.get_params()
        self.set_params(arg_params_, aux_params_)
        if epoch_end_callback is not None:
            for callback in _as_list(epoch_end_callback):
                callback(epoch, self.symbol, arg_params_, aux_params_)

        if eval_data:
            res = self.score(eval_data, validation_metric,
                             score_end_callback=eval_end_callback,
                             batch_end_callback=eval_batch_end_callback,
                             epoch=epoch)
            for name, val in res:
                self.logger.info('Epoch[%d] Validation-%s=%f',
                                 epoch, name, val)
            _tele.ledger.note_eval([('val-%s' % n, v) for n, v in res],
                                   epoch=epoch)
        # score() suspends the hang watchdog on exit (standalone-eval
        # semantics); mid-fit the NEXT epoch is coming, so re-arm here
        # — a host lost during eval wedges exactly the next epoch's
        # first collective, and that window must stay covered
        _tele.watchdog.note_progress('fit.epoch_end')

    # -- parameter contract (implemented by subclasses) --------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {('arg:%s' % k): v for k, v in arg_params.items()}
        save_dict.update({('aux:%s' % k): v for k, v in aux_params.items()})
        nd.save(fname, save_dict)

    def load_params(self, fname):
        save_dict = nd.load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(':', 1)
            if arg_type == 'arg':
                arg_params[name] = value
            elif arg_type == 'aux':
                aux_params[name] = value
            else:
                raise ValueError('Invalid param file ' + fname)
        self.set_params(arg_params, aux_params)

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        assert not merge_multi_context
        return []

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        assert not states and not value

    def install_monitor(self, mon):
        raise NotImplementedError()

    def prepare(self, data_batch):
        pass

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req='write'):
        raise NotImplementedError()

    def init_optimizer(self, kvstore='local', optimizer='sgd',
                       optimizer_params=(('learning_rate', 0.01),),
                       force_init=False):
        raise NotImplementedError()


def _as_list(obj):
    if isinstance(obj, list):
        return obj
    return [obj]


def _cur_lr(opt):
    """The optimizer's CURRENT effective base learning rate (scheduler
    honored), or None — the run ledger's lr scalar."""
    if opt is None:
        return None
    try:
        if getattr(opt, 'lr_scheduler', None) is not None:
            return float(opt.lr_scheduler(opt.num_update))
        return float(opt.lr)
    except Exception:  # noqa: BLE001 — exotic optimizer: no lr scalar
        return None
