"""Pre-imported worker zygote: fork()-spawned pods skip cold imports.

Submit→first-step latency (north-star #2, BASELINE.md row 2) is dominated
on CPU workers by each pod paying a fresh interpreter + ``import jax`` +
framework imports before rendezvous even starts. The zygote is the
forkserver answer (the same trick CPython's ``multiprocessing``
forkserver and Ray's worker pool use): one helper process imports the
heavy modules ONCE — crucially, importing jax does NOT initialize any
backend, so the fork inherits warm code with no device state — then forks
a child per pod in ~milliseconds.

Protocol (one connection per pod, held open for its life):
  daemon -> zygote: one JSON line {"argv": [...], "env": {...}, "log": p}
  zygote -> daemon: {"pid": N}            after the fork
  zygote -> daemon: {"exit": code}        when the child exits

The child applies the pod env (backends are uninitialized, so XLA_FLAGS
still takes effect; what jax read at import — JAX_PLATFORMS,
JAX_COMPILATION_CACHE_DIR — is re-applied through jax.config), points
stdout/stderr at the pod log (omitting ``log`` inherits the zygote's own
stdout — the pod log, for the in-pod kube form), and runs ``argv`` —
which must be the ``[sys.executable, "-m", module, *args]`` form
(anything else is the daemon's cue to fall back to a plain spawn).

Two listener forms behind one serve():

- a unix socket path — ``LocalProcessCluster(warm_pool=True)`` owns one
  zygote per daemon and routes eligible pods through it;
- ``tcp://host:port`` (port 0 = ephemeral) — the NODE-RESIDENT form: a
  pre-warmed standby pod on the Kube backend runs this as its main
  command, and the WarmPoolController claims the pod and delivers the
  worker argv over the pod network (controller/warmpool.py). The bound
  address is announced via ``--announce-file`` (and the
  KFT_ZYGOTE_ANNOUNCE env the kubelet injects) so the node agent can
  publish it as a pod annotation.

SECURITY (tcp form): a fork server reachable over the pod network is an
arbitrary-code-execution endpoint, so it is token-fenced — when
``KFT_ZYGOTE_TOKEN`` is set (the WarmPoolController stamps a random one
into every standby pod's env), a request whose ``"token"`` field does not
match is refused before any fork. The token lives in the pod spec, i.e.
the same trust domain as the pod's ServiceAccount: reading it requires
apiserver pod-read rights, which already imply claim rights. Deployments
should ALSO scope a NetworkPolicy to the operator, defense in depth.

RECLAIM (the warm-pool return arc): an early-stopped trial's pod goes
BACK to the pool instead of being deleted. The controller sends
``{"reclaim": true, "token": <current>, "new_token": <fresh>}``: the
zygote SIGKILLs the live forked worker's process group (the child called
setsid, so its pgid is its pid), ROTATES the accepted token, and acks
``{"reclaimed": true, "killed": [...]}``. Token rotation is the fence
that makes the returned pod safe to re-claim: a stale claimant replaying
the old token — e.g. a late exec from the trial that was just stopped —
is refused before any fork. The accept loop survives worker death, so
the same resident zygote serves the next claim with imports still warm.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading

# fork() while sibling handler threads are mid-malloc/mid-lock is the
# classic threaded-fork deadlock: the child inherits a heap/lock snapshot
# whose owners don't exist there. Serializing forks doesn't remove that
# hazard entirely (accept loops and CPython runtime threads still exist),
# but it guarantees no two handler threads interleave fork bookkeeping,
# which is where the observed wedges live. Held only around os.fork()
# itself — waitpid runs unlocked so forks never serialize on pod LIFETIME.
_fork_lock = threading.Lock()


def _preimport() -> None:
    """The heavy import set a training worker OR serving replica pays
    cold. Serving joined in the fleet round: a warm-pool scale-up forks
    the predictor runtime from this zygote, so its module tree must be
    resident too (none of it initializes a backend — asserted below)."""
    import jax  # noqa: F401
    import jax.numpy  # noqa: F401
    import numpy  # noqa: F401
    import optax  # noqa: F401

    from kubeflow_tpu import models, serving, training  # noqa: F401
    from kubeflow_tpu.rendezvous import bootstrap  # noqa: F401
    from kubeflow_tpu.serving import runtime  # noqa: F401

    # invariant the whole design rests on: imports must not have touched a
    # backend (a forked live TPU/CPU client would be corrupt)
    from jax._src import xla_bridge

    assert not xla_bridge._backends, "zygote initialized a JAX backend"


def _run_child(req: dict) -> None:
    """In the forked child: become the pod process."""
    os.setsid()                              # own signal group, like Popen
    try:
        # die with the zygote: a killed zygote must not leave orphaned
        # workers holding devices (PR_SET_PDEATHSIG=1; the handler thread
        # that forked us lives in waitpid until we exit, so the Linux
        # thread-death caveat cannot fire early)
        import ctypes

        ctypes.CDLL(None, use_errno=True).prctl(1, 9, 0, 0, 0)
    except Exception:
        pass
    argv = req["argv"]
    env = req.get("env") or {}
    os.environ.update({k: str(v) for k, v in env.items()})
    if req.get("log"):
        fd = os.open(req["log"],
                     os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        os.dup2(fd, 1)
        os.dup2(fd, 2)
        os.close(fd)
    # no "log": inherit the zygote's own stdout/stderr — in the standby-pod
    # form that IS the pod log, which is where the worker should write
    # jax was imported before the fork, so it read the ZYGOTE's values of
    # these at import; the pod's must be applied by hand
    import jax

    for var, option in (("JAX_PLATFORMS", "jax_platforms"),
                        ("JAX_COMPILATION_CACHE_DIR",
                         "jax_compilation_cache_dir")):
        if env.get(var):
            jax.config.update(option, str(env[var]))
    # [python, -m, module, *args] — validated by the daemon before routing
    module = argv[2]
    sys.argv = [argv[0]] + argv[3:]
    import runpy

    runpy.run_module(module, run_name="__main__", alter_sys=True)


def serve(listen: str, announce_file: str | None = None) -> int:
    _preimport()
    if listen.startswith("tcp://"):
        host, _, port = listen[len("tcp://"):].rpartition(":")
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((host or "0.0.0.0", int(port or 0)))
        bound = f"{srv.getsockname()[0]}:{srv.getsockname()[1]}"
    else:
        srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            os.unlink(listen)
        except FileNotFoundError:
            pass
        srv.bind(listen)
        bound = listen
    srv.listen(64)
    if announce_file:
        # atomic announce: the node agent polls for this file and publishes
        # the address as a pod annotation (a partially written file must
        # never be read as an address)
        tmp = f"{announce_file}.tmp"
        with open(tmp, "w") as f:
            f.write(bound)
        os.replace(tmp, announce_file)
    print(f"zygote ready on {bound}", flush=True)

    # the accepted token is MUTABLE state (reclaim rotates it) and the
    # forked-worker pids are tracked so a reclaim can kill them — both
    # shared across handler threads behind one lock
    state = {"token": os.environ.get("KFT_ZYGOTE_TOKEN", "")}
    live_pids: set = set()
    state_lock = threading.Lock()

    def handle(conn: socket.socket) -> None:
        try:
            buf = b""
            while not buf.endswith(b"\n"):
                chunk = conn.recv(65536)
                if not chunk:
                    return
                buf += chunk
            req = json.loads(buf)
            with state_lock:
                token = state["token"]
            if token and req.get("token") != token:
                # unauthenticated peer on the pod network — or a STALE
                # claimant replaying a pre-reclaim token: refuse BEFORE
                # any fork (see module docstring, SECURITY / RECLAIM)
                conn.sendall(json.dumps(
                    {"error": "bad token"}).encode() + b"\n")
                return
            if req.get("reclaim"):
                # warm-pool return arc: kill the live worker's process
                # group and rotate the token BEFORE acking, so by the
                # time the pod shows standby again the old trial cannot
                # fork and the old token cannot exec
                import signal

                with state_lock:
                    doomed = list(live_pids)
                    if req.get("new_token"):
                        state["token"] = str(req["new_token"])
                killed = []
                for pid in doomed:
                    try:
                        os.killpg(pid, signal.SIGKILL)
                        killed.append(pid)
                    except (ProcessLookupError, PermissionError):
                        pass        # already gone: reclaim is idempotent
                conn.sendall(json.dumps(
                    {"reclaimed": True, "killed": killed}
                ).encode() + b"\n")
                return
            with _fork_lock:
                pid = os.fork()
            if pid == 0:
                try:
                    srv.close()
                    conn.close()
                    _run_child(req)
                    os._exit(0)
                except SystemExit as e:
                    # CPython semantics: int -> that code; None -> 0;
                    # anything else (sys.exit("message")) -> stderr + 1
                    if e.code is None:
                        os._exit(0)
                    if isinstance(e.code, int):
                        os._exit(e.code)
                    print(e.code, file=sys.stderr)
                    os._exit(1)
                except BaseException:
                    import traceback

                    traceback.print_exc()
                    os._exit(1)
            with state_lock:
                live_pids.add(pid)
            conn.sendall(json.dumps({"pid": pid}).encode() + b"\n")
            _, status = os.waitpid(pid, 0)
            with state_lock:
                live_pids.discard(pid)
            code = os.waitstatus_to_exitcode(status)
            try:
                conn.sendall(json.dumps({"exit": code}).encode() + b"\n")
            except OSError:
                pass                        # daemon gone; child is reaped
        finally:
            conn.close()

    while True:
        conn, _ = srv.accept()
        threading.Thread(target=handle, args=(conn,), daemon=True).start()


def main(argv=None) -> int:
    args = list(argv if argv is not None else sys.argv[1:])
    announce = None
    if "--announce-file" in args:
        i = args.index("--announce-file")
        try:
            announce = args[i + 1]
        except IndexError:
            print("--announce-file needs a path", file=sys.stderr)
            return 2
        del args[i:i + 2]
    # the kubelet-injected announce convention: a node agent that spawns
    # this pod sets KFT_ZYGOTE_ANNOUNCE so it can learn the bound address
    # without rewriting the pod command
    if announce is None:
        announce = os.environ.get("KFT_ZYGOTE_ANNOUNCE") or None
    if len(args) != 1:
        print("usage: python -m kubeflow_tpu.rendezvous.zygote "
              "<socket-path | tcp://host:port> [--announce-file PATH]",
              file=sys.stderr)
        return 2
    return serve(args[0], announce_file=announce)


if __name__ == "__main__":
    sys.exit(main())
