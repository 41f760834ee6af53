#!/usr/bin/env python
"""Gang-scheduled supervision of a real multi-host training job.

``tools/train_supervisor.py`` relaunches ONE dying process. A
multi-host job is W processes in one ``jax.distributed`` gang, and it
dies as a unit: when one worker exits unclean — the hang watchdog's
abort (85), a host loss (113), an OOM kill, a segfault — the survivors
are wedged inside DCN collectives that can never complete. No
per-process restart can help them; the whole gang must be torn down
and relaunched. This tool is that tier::

    python tools/gang_supervisor.py -n 4 -- python train.py
    MXTPU_RESTART_MAX=5 python tools/gang_supervisor.py -n 4 \
        --elastic-min-hosts 2 --log-dir /mnt/run1/logs -- python train.py

Per attempt it launches W workers with the same env protocol
``tools/launch.py`` speaks — ``MXTPU_COORDINATOR`` (a FRESH port every
attempt: the previous coordinator's socket may linger, and on jax
0.4.x a coordinator bind conflict is unrecoverable in-process),
``MXTPU_NUM_HOSTS``, ``MXTPU_HOST_ID`` — prefixes each worker's output
``[h<i>]``, and supervises them as a GANG:

- ANY worker exiting unclean tears the rest down (SIGTERM, a grace
  period, SIGKILL) and relaunches the whole gang against the shared
  restart budget (MXTPU_RESTART_MAX / MXTPU_RESTART_BACKOFF). Worker 0
  IS the coordinator, so coordinator loss is just the i=0 case of the
  same path.
- the liveness tier (--liveness / MXTPU_SUPERVISOR_LIVENESS) watches
  every worker's telemetry JSONL; one wedged worker (no growth past
  the threshold) fails the gang the same way.
- ``--elastic-min-hosts M`` (MXTPU_GANG_MIN_HOSTS): a relaunch
  triggered by a host-loss exit (code 113) proceeds with one FEWER
  worker while more than M remain — the relaunched job sees the
  smaller MXTPU_NUM_HOSTS, ``io.auto_shard`` re-derives every shard
  range, and the checkpoint restore reshards onto the smaller mesh
  (reshard-on-restore, docs/reliability.md). Other failure kinds
  relaunch at full width: a watchdog abort or an OOM kill says nothing
  about the HOST being gone.
- restart-from-last-good rides the children's own MXTPU_CKPT_RESUME
  path, restoring the cross-host-AGREED ``last_good.step`` — the gang
  checkpoint tier guarantees every host certified it, so a relaunch
  can never restore divergent steps.

With ``--log-dir`` (or MXTPU_TELEMETRY_PATH set) worker i writes its
telemetry to ``<dir>/h<i>.jsonl`` and gang restart records append to
``<dir>/gang.jsonl`` — exactly the layout
``python tools/telemetry_report.py <dir>`` globs into the per-host
comparison.

Exit code: 0 when every worker of the final attempt exits clean;
otherwise the FIRST failing worker's code (the root cause — survivors
die of follow-on errors), with the train_supervisor conventions kept:
a liveness kill whose child exited 0 reports 1, CLI misuse (2) never
retries. Budget/backoff/liveness/record code is shared with
tools/train_supervisor.py.
"""
# This supervisor stays off JAX (stdlib imports only): it never holds the
# chip, so the children it starts are free to take it.
import argparse
import os
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import launch as _launch                    # noqa: E402
import train_supervisor as _sup             # noqa: E402

HOST_LOSS_EXIT = 113   # mirrored from mxnet_tpu/faults.py (no framework
                       # import here, same rule as train_supervisor)
_POLL_S = 0.1


def _reserve_coord_port(exclude):
    """(socket, port): a reserved coordinator port not in ``exclude``
    (every attempt gets a port no previous attempt of this gang used —
    a dying predecessor cannot alias a fresh gang's rendezvous). The
    reserving socket stays OPEN until immediately before worker 0
    spawns: on jax 0.4.x a coordinator bind conflict dies in grpc
    before Python can catch it, so the widest pick-to-bind window in
    the codebase — W forks plus worker 0's jax import — must not leave
    the port up for grabs."""
    sock, port = _launch._reserve_port()
    for _ in range(64):
        if port not in exclude:
            break
        sock.close()
        sock, port = _launch._reserve_port()
    return sock, port


def _worker_env(base, idx, hosts, port, log_dir):
    env = dict(base)
    env['MXTPU_COORDINATOR'] = '127.0.0.1:%d' % port
    env['MXTPU_NUM_HOSTS'] = str(hosts)
    env['MXTPU_HOST_ID'] = str(idx)
    # workers orphaned by a dead coordinator must fail fast so the
    # gang can be torn down and relaunched on a fresh port — jax's own
    # join default is 5 minutes. An operator's explicit setting wins
    env.setdefault('MXTPU_COORD_TIMEOUT', '60')
    if log_dir:
        env['MXTPU_TELEMETRY_PATH'] = os.path.join(log_dir,
                                                   'h%d.jsonl' % idx)
    return env


class _Liveness:
    """Per-worker stall watches over the h<i>.jsonl files: the
    single-child liveness rule (train_supervisor.FileStallWatch — ONE
    policy for both supervision tiers), applied per gang member."""

    def __init__(self, paths, secs):
        self.secs = secs
        self.watches = [_sup.FileStallWatch(p, secs) for p in paths]

    def stalled(self, alive=None):
        """Index of the first LIVE worker past the stall threshold, or
        None. ``alive`` masks workers that already exited — a finished
        worker's naturally-stale file must not shadow the stall check
        of the still-wedged workers after it."""
        if not self.secs:
            return None
        for i, watch in enumerate(self.watches):
            if alive is not None and not alive[i]:
                continue
            if watch.stalled() is not None:
                return i
        return None


def _teardown(workers, grace=_sup._TERM_GRACE_S):
    """SIGTERM every live worker, one shared grace period, SIGKILL the
    rest. The survivors are wedged inside collectives that can never
    complete — there is nothing to wait for past the grace."""
    for p in workers:
        if p.poll() is None:
            p.terminate()
    deadline = time.time() + grace
    for p in workers:
        if p.poll() is None:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                pass
    for p in workers:
        if p.poll() is None:
            p.kill()
            p.wait()
    # every worker is dead: drain the [h<i>] pumps so the buffered
    # tail of the failure (the root-cause traceback) reaches the
    # supervisor's streams before any record/return
    _launch.join_pumps(workers)


def _wait_gang(workers, liveness):
    """Block until the gang resolves. Returns ``(failed_idx, code,
    timed_out)``: (None, 0, False) = every worker exited clean;
    otherwise the FIRST unclean exit in completion order, or the first
    liveness stall (code None until the kill)."""
    while True:
        alive = []
        for i, p in enumerate(workers):
            code = p.poll()
            alive.append(code is None)
            if code is not None and code != 0:
                return i, code, False
        if not any(alive):
            return None, 0, False
        i = liveness.stalled(alive=alive)
        if i is not None:
            return i, None, True
        time.sleep(_POLL_S)


def run_gang(cmd, hosts, restart_max, backoff, log_path, log_dir,
             liveness=0.0, min_hosts=0, quiet=False):
    """Supervise ``cmd`` as a ``hosts``-worker gang; returns the final
    exit code (train_supervisor conventions)."""
    attempts = 0
    used_ports = set()
    base_env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env['PYTHONPATH'] = (repo + os.pathsep + base_env['PYTHONPATH']
                              if base_env.get('PYTHONPATH') else repo)
    # cumulative lost-work seconds across gang relaunches
    # (train_supervisor's accounting, priced once per gang attempt —
    # the gang dies as a unit); every relaunched worker reads it back
    # as MXTPU_GOODPUT_LOST_S and reports prior_lost_s in its goodput
    # record
    lost_total = _sup._env_float('MXTPU_GOODPUT_LOST_S', 0.0)
    while True:
        base_env['MXTPU_GOODPUT_LOST_S'] = '%.3f' % lost_total
        coord_sock, port = _reserve_coord_port(used_ports)
        used_ports.add(port)
        t0 = time.time()
        workers = []
        try:
            envs = [_worker_env(base_env, i, hosts, port, log_dir)
                    for i in range(hosts)]
            # worker 0 (spawned first) binds the coordinator: release
            # the reservation at the last possible moment
            coord_sock.close()
            for i in range(hosts):
                workers.append(_launch.start_worker(cmd, envs[i], i))
        except OSError as e:
            print('gang_supervisor: cannot launch %r (%s)' % (cmd[0], e),
                  file=sys.stderr)
            _teardown(workers)
            return 127
        watch = _Liveness([os.path.join(log_dir, 'h%d.jsonl' % i)
                           for i in range(hosts)] if log_dir else [],
                          liveness)
        try:
            idx, code, timed_out = _wait_gang(workers, watch)
        except KeyboardInterrupt:
            # operator stop: forward and leave — never a fault to retry
            for p in workers:
                if p.poll() is None:
                    p.send_signal(signal.SIGINT)
            _teardown(workers, grace=30.0)
            code = max((p.returncode or 0) for p in workers)
            _sup._record(log_path, {
                'type': 'restart', 'attempt': attempts, 'final': True,
                'reason': 'KeyboardInterrupt', 'exit_code': code,
                'host': 0, 'hosts': hosts})
            return code
        elapsed = time.time() - t0
        if idx is None:
            _launch.join_pumps(workers)   # all exited clean: drain tails
            if attempts and not quiet:
                print('gang_supervisor: gang completed after %d '
                      'restart(s)' % attempts, file=sys.stderr)
            _sup._record(log_path, {
                'type': 'restart', 'attempt': attempts, 'final': True,
                'reason': 'clean_exit', 'exit_code': 0, 'host': 0,
                'hosts': hosts})
            return 0
        if timed_out and not quiet:
            print('gang_supervisor: worker %d wrote no telemetry records '
                  'for %.0fs (liveness %.0fs) — killing the wedged gang'
                  % (idx, liveness, liveness), file=sys.stderr)
        if timed_out:
            code = _sup._kill_child(workers[idx])
        # one worker down (or wedged): the rest are hostages of
        # collectives that cannot complete — take the gang down as a
        # unit before deciding anything else
        _teardown(workers)
        no_retry = (code in _sup._NO_RETRY_CODES and not timed_out)
        if no_retry or attempts >= restart_max:
            _sup._record(log_path, {
                'type': 'restart', 'attempt': attempts, 'final': True,
                'reason': 'usage' if no_retry else 'budget_exhausted',
                'exit_code': code, 'worker': idx, 'host': idx,
                'hosts': hosts})
            if not quiet:
                print('gang_supervisor: giving up after %d attempt(s) '
                      '(worker %d: %s)'
                      % (attempts + 1, idx, _sup._describe(code)),
                      file=sys.stderr)
            # a liveness kill whose SIGTERM handler exited 0 is still
            # an abandoned run (train_supervisor's rule)
            return code if not (timed_out and code == 0) else 1
        attempts += 1
        next_hosts = hosts
        if code == HOST_LOSS_EXIT and min_hosts and hosts > min_hosts:
            # the worker reported its HOST gone (exit 113): relaunch
            # the survivors as a smaller gang. The relaunched job sees
            # the smaller MXTPU_NUM_HOSTS, io.auto_shard re-derives
            # shard coverage, and the restore reshards the agreed
            # last-good checkpoint onto the smaller mesh
            next_hosts = hosts - 1
        delay = _sup.backoff_delay(attempts, backoff)
        lost = _sup.lost_work_secs(elapsed)
        lost_total += lost
        _sup._record(log_path, {
            'type': 'restart', 'attempt': attempts,
            'reason': 'liveness_timeout' if timed_out else 'worker_exit',
            'message': 'worker %d: %s' % (idx, _sup._describe(code)),
            'exit_code': code, 'worker': idx, 'host': idx,
            'hosts': hosts, 'next_hosts': next_hosts,
            'coordinator_port': port,
            'elapsed_s': round(elapsed, 1),
            'lost_s': round(lost, 1),
            'lost_total_s': round(lost_total, 1),
            'backoff_s': delay})
        if not quiet:
            print('gang_supervisor: attempt %d/%d — worker %d died '
                  '(%s after %.0fs); relaunching %d worker(s) on a '
                  'fresh coordinator port in %.1fs'
                  % (attempts, restart_max, idx, _sup._describe(code),
                     elapsed, next_hosts, delay), file=sys.stderr)
        hosts = next_hosts
        if delay:
            try:
                time.sleep(delay)
            except KeyboardInterrupt:
                _sup._record(log_path, {
                    'type': 'restart', 'attempt': attempts, 'final': True,
                    'reason': 'KeyboardInterrupt', 'exit_code': code,
                    'host': 0, 'hosts': hosts})
                return code


def main(argv=None):
    p = argparse.ArgumentParser(
        description='Launch W workers as one jax.distributed gang and '
                    'supervise them as a unit: any unclean worker exit '
                    'tears the gang down and relaunches it on a fresh '
                    'coordinator port against the MXTPU_RESTART_* '
                    'budget.')
    p.add_argument('-n', '--num-hosts', type=int, required=True,
                   help='worker (process) count of the gang')
    p.add_argument('--restart-max', type=int, default=None,
                   help='restart budget (default: MXTPU_RESTART_MAX or 3)')
    p.add_argument('--backoff', type=float, default=None,
                   help='base backoff seconds '
                        '(default: MXTPU_RESTART_BACKOFF or 2)')
    p.add_argument('--elastic-min-hosts', type=int, default=None,
                   help='relaunch a host-loss (exit 113) with one fewer '
                        'worker while more than this many remain '
                        '(default: MXTPU_GANG_MIN_HOSTS or 0 = never '
                        'shrink)')
    p.add_argument('--log-dir', default=None,
                   help="per-worker telemetry JSONLs land here as "
                        "h<i>.jsonl and restart records as gang.jsonl "
                        "(default: the directory of MXTPU_TELEMETRY_PATH "
                        "when set)")
    p.add_argument('--log', default=None,
                   help='JSONL file for gang restart records (default: '
                        '<log-dir>/gang.jsonl)')
    p.add_argument('--liveness', type=float, default=None,
                   help='kill + relaunch the gang when any worker\'s '
                        'telemetry JSONL stops growing for this many '
                        'seconds (default: MXTPU_SUPERVISOR_LIVENESS or '
                        '0 = off; needs MXTPU_TELEMETRY=1 in the '
                        'children and a --log-dir)')
    p.add_argument('--quiet', action='store_true',
                   help='suppress supervisor stderr chatter')
    p.add_argument('cmd', nargs=argparse.REMAINDER,
                   help='training command (prefix with -- )')
    args = p.parse_args(argv)
    cmd = list(args.cmd)
    if cmd and cmd[0] == '--':
        cmd = cmd[1:]
    if not cmd:
        p.error('no training command given (append: -- python train.py ...)')
    if args.num_hosts < 1:
        p.error('-n must be >= 1')
    restart_max = args.restart_max if args.restart_max is not None \
        else _sup._env_int('MXTPU_RESTART_MAX', 3)
    backoff = args.backoff if args.backoff is not None \
        else _sup._env_float('MXTPU_RESTART_BACKOFF', 2.0)
    min_hosts = args.elastic_min_hosts if args.elastic_min_hosts is not None \
        else _sup._env_int('MXTPU_GANG_MIN_HOSTS', 0)
    liveness = args.liveness if args.liveness is not None \
        else _sup._env_float('MXTPU_SUPERVISOR_LIVENESS', 0.0)
    log_dir = args.log_dir
    if log_dir is None and os.environ.get('MXTPU_TELEMETRY_PATH'):
        log_dir = os.path.dirname(os.path.abspath(
            os.environ['MXTPU_TELEMETRY_PATH']))
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    log_path = args.log or (os.path.join(log_dir, 'gang.jsonl')
                            if log_dir else None)
    if liveness > 0 and not log_dir:
        print('gang_supervisor: --liveness needs a --log-dir (or '
              'MXTPU_TELEMETRY_PATH) so per-worker h<i>.jsonl files '
              'exist to watch — liveness disabled', file=sys.stderr)
        liveness = 0.0
    if not args.quiet and not os.environ.get('MXTPU_CKPT_DIR'):
        print('gang_supervisor: MXTPU_CKPT_DIR is not set — gang '
              'relaunches will rerun from step 0 (set MXTPU_CKPT_DIR '
              'and MXTPU_CKPT_EVERY so relaunches resume from the '
              'cross-host-agreed last-good checkpoint)', file=sys.stderr)
    return run_gang(cmd, args.num_hosts, restart_max, backoff, log_path,
                    log_dir, liveness=liveness, min_hosts=min_hosts,
                    quiet=args.quiet)


if __name__ == '__main__':
    sys.exit(main())
