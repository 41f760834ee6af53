#!/usr/bin/env python
"""Launch a distributed KVStore job: scheduler + servers + workers.

Reference: tools/launch.py:29-47 (delegates to the dmlc-core tracker for
ssh/mpi/yarn/local). This implements the `local` launcher — every role runs
as a local subprocess with the DMLC_* env protocol
(include/mxnet/kvstore.h:244-301):

    python tools/launch.py -n 4 -s 2 python my_training_script.py

Server and scheduler processes just `import mxnet_tpu`; the role loop in
kvstore_server.init_server_module_if_needed takes over (reference
python/mxnet/kvstore_server.py:75).

Worker stdout/stderr is prefixed ``[h<i>]`` so interleaved multi-process
output attributes to a host, and the launcher's exit code is the FIRST
worker failure in completion order (the root cause — later workers die
of follow-on collective errors with less informative codes).

This launcher runs ONE attempt; it does not supervise. For gang
semantics — tear down the survivors when one worker dies unclean,
relaunch the whole job on a fresh coordinator port against a restart
budget, optionally shrink the worker set after a host loss — wrap the
job in ``tools/gang_supervisor.py`` instead.
"""
# This supervisor stays off JAX (stdlib imports only): it never holds the
# chip, so the children it starts are free to take it.
import argparse
import os
import socket
import subprocess
import sys
import threading
import time


def _reserve_port():
    """(socket, port): an OS-assigned port with the reserving socket
    still OPEN — the caller closes it immediately before spawning the
    process that binds it. The old close-at-pick free_port() left the
    port up for grabs for the WHOLE setup stretch (spawning a scheduler
    + N servers); this shrinks the race to the close->bind window, and
    init_multihost's bounded join retry covers that residue."""
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(('127.0.0.1', 0))
    return s, s.getsockname()[1]


def _pump(stream, sink, prefix):
    """Forward one worker pipe line-by-line with the ``[h<i>]`` host
    prefix (daemon thread; binary-safe, flushed per line so interleaved
    gang output stays attributable)."""
    try:
        for line in iter(stream.readline, b''):
            sink.write(prefix + line)
            sink.flush()
    except ValueError:          # sink closed at interpreter teardown
        pass
    finally:
        stream.close()


def start_worker(cmd, env, idx, out=None, err=None):
    """Spawn one worker with ``[h<idx>]``-prefixed stdout/stderr pumps.
    ``out``/``err`` default to this process's binary stdio (the gang
    supervisor passes its own sinks)."""
    prefix = ('[h%d] ' % idx).encode()
    env = dict(env)
    # the pipes below replace the tty the worker used to inherit: a
    # Python worker would block-buffer ~8KB, delaying live output and
    # LOSING the buffered tail — the diagnostic the prefixing exists
    # for — when a wedged worker is SIGKILLed. Harmless for non-Python
    # commands; an operator's explicit setting wins
    env.setdefault('PYTHONUNBUFFERED', '1')
    p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE)
    p._mxtpu_pumps = []
    for stream, sink in ((p.stdout, out or sys.stdout.buffer),
                        (p.stderr, err or sys.stderr.buffer)):
        t = threading.Thread(target=_pump, args=(stream, sink, prefix),
                             daemon=True)
        t.start()
        p._mxtpu_pumps.append(t)
    return p


def join_pumps(workers, timeout=5.0):
    """Drain the output pumps of EXITED workers before the launcher
    process returns: the pumps are daemon threads, and interpreter
    shutdown would otherwise drop the buffered tail of a failing
    worker's pipe — exactly the root-cause traceback the [h<i>]
    prefixing exists to preserve. Bounded: the workers are dead, so
    EOF is a read away."""
    deadline = time.time() + timeout
    for p in workers:
        for t in getattr(p, '_mxtpu_pumps', ()):
            t.join(timeout=max(0.1, deadline - time.time()))


def wait_first_failure(workers, poll_s=0.05):
    """Wait for every worker; return the exit code of the FIRST one to
    fail in COMPLETION order (the root cause of a gang death — the old
    list-order scan reported whichever low-index worker died last of a
    follow-on collective error), or 0 when all exit clean."""
    rc = 0
    pending = dict(enumerate(workers))
    while pending:
        for i, p in sorted(pending.items()):
            code = p.poll()
            if code is None:
                continue
            del pending[i]
            if code != 0 and rc == 0:
                rc = code
        if pending:
            time.sleep(poll_s)
    return rc


def main():
    ap = argparse.ArgumentParser(description='Launch a distributed job')
    ap.add_argument('-n', '--num-workers', type=int, required=True)
    ap.add_argument('-s', '--num-servers', type=int, default=None,
                    help='default: same as --num-workers')
    ap.add_argument('--launcher', choices=['local'], default='local')
    ap.add_argument('--sync-dst-dir', default=None,
                    help='accepted for reference CLI compat; unused locally')
    ap.add_argument('command', nargs=argparse.REMAINDER)
    args = ap.parse_args()
    # REMAINDER keeps a leading '--' separator; drop it (reference
    # launch.py accepts both `launch.py -n 2 cmd` and `-n 2 -- cmd`)
    if args.command and args.command[0] == '--':
        args.command = args.command[1:]
    if not args.command:
        ap.error('no command given')
    num_servers = (args.num_servers if args.num_servers is not None
                   else args.num_workers)

    # reserve both rendezvous ports with OPEN sockets until their
    # binding process is about to spawn (see _reserve_port)
    root_sock, root_port = _reserve_port()
    coord_sock, coord_port = _reserve_port()
    base_env = dict(os.environ)
    base_env.update({
        'DMLC_PS_ROOT_URI': '127.0.0.1',
        'DMLC_PS_ROOT_PORT': str(root_port),
        'DMLC_NUM_WORKER': str(args.num_workers),
        'DMLC_NUM_SERVER': str(num_servers),
        # jax.distributed bridge (parallel/multihost.py): workers can
        # join one SPMD job with XLA collectives instead of (or beside)
        # the PS tier
        'MXTPU_COORDINATOR': '127.0.0.1:%d' % coord_port,
        'MXTPU_NUM_HOSTS': str(args.num_workers),
    })
    # role processes must be able to import mxnet_tpu from any cwd
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env['PYTHONPATH'] = (repo + os.pathsep + base_env['PYTHONPATH']
                              if base_env.get('PYTHONPATH') else repo)
    role_cmd = [sys.executable, '-c', 'import mxnet_tpu']

    procs, workers = [], []
    # no PS tier requested (e.g. pure jax.distributed jobs): skip the
    # scheduler too, or workers would leave it blocking 20 s at exit
    scheduler_count = 1 if num_servers > 0 else 0
    root_sock.close()           # the scheduler binds it next
    try:
        for role, count, cmd in [('scheduler', scheduler_count, role_cmd),
                                 ('server', num_servers, role_cmd)]:
            for i in range(count):
                env = dict(base_env)
                env['DMLC_ROLE'] = role
                procs.append(subprocess.Popen(cmd, env=env))
        coord_sock.close()      # worker 0 binds the coordinator next
        for i in range(args.num_workers):
            env = dict(base_env)
            env['DMLC_ROLE'] = 'worker'
            env['MXTPU_HOST_ID'] = str(i)
            p = start_worker(args.command, env, i)
            procs.append(p)
            workers.append(p)
        rc = wait_first_failure(workers)
        join_pumps(workers)
        for p in procs:
            if p not in workers:
                try:
                    p.wait(timeout=20)
                except subprocess.TimeoutExpired:
                    p.terminate()
        return rc
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


if __name__ == '__main__':
    sys.exit(main())
