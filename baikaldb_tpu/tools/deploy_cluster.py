"""Mini-cluster deploy: 1 meta + N store daemons + 1 MySQL frontend, all
real processes on one host (the reference deployment shape,
/root/reference/sysbench/baikaldb_deploy_scripts/init.sh: baikalMeta +
3 baikalStore + baikaldb).

Usage:
    python -m baikaldb_tpu.tools.deploy_cluster [--stores 3] \
        [--base-port 9100] [--mysql-port 28000]

Prints one line per process and stays in the foreground; Ctrl-C tears the
cluster down.  ``spawn_cluster`` is the library entry the e2e test uses.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

from ..utils.net import RpcClient

# Meta and store daemons hold no chip by design today: a chip belongs to
# one process, and on a one-chip host that process is the frontend, where
# the analytical operators run.  The daemons' own jax work (compiled
# pushed-down fragments) is pinned to the CPU explicitly.  A frontend
# inherits the caller's platform; a second frontend on a one-chip host then
# fails to initialise its backend, loudly.
_DAEMON_ENV = {"JAX_PLATFORMS": "cpu"}


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _spawn(args: list[str], env_extra: dict | None = None) -> subprocess.Popen:
    log_dir = os.environ.get("BK_CLUSTER_LOGS")
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        name = args[0].rsplit(".", 1)[-1] + "_" + \
            "_".join(a.replace(":", "_").replace("/", "_")
                     for a in args[1:] if not a.startswith("--"))
        out = open(os.path.join(log_dir, name + ".log"), "ab")
    else:
        out = subprocess.DEVNULL
    return subprocess.Popen([sys.executable, "-m"] + args,
                            env=dict(os.environ, **(env_extra or {})),
                            cwd=_repo_root(), stdout=out, stderr=out)


def _wait_ping(address: str, timeout: float = 15.0) -> None:
    deadline = time.monotonic() + timeout
    client = RpcClient(address, timeout=1.0)
    while time.monotonic() < deadline:
        if client.try_call("ping") is not None:
            client.close()
            return
        time.sleep(0.2)
    raise TimeoutError(f"no ping from {address}")


def spawn_cluster(n_stores: int = 3, base_port: int = 9100,
                  mysql_port: int = 0, n_mysql: int = 1,
                  aot_dir: str = "", cold_dir: str = ""):
    """-> (meta_address, {"meta", "stores", "mysql", "mysqls"}).
    mysql_port=0 skips frontends (tests drive Session directly);
    ``n_mysql`` > 1 spawns frontends on consecutive ports — the
    reference's N-baikaldb deploy (throughput scales per frontend
    process; see RemoteRowTier's single-WRITER note).  ``aot_dir`` /
    ``cold_dir`` plumb the daemons' fragment-artifact blob tier and
    cold-segment filesystem (per-store subdirectories, so daemons warm
    fragment programs from disk and fold their own cold tier in place)."""
    meta_addr = f"127.0.0.1:{base_port}"
    procs = {"meta": _spawn(["baikaldb_tpu.server.meta_server",
                             "--address", meta_addr,
                             "--peer-count", str(n_stores)], _DAEMON_ENV),
             "stores": [], "mysql": None, "mysqls": []}
    _wait_ping(meta_addr)
    for i in range(1, n_stores + 1):
        addr = f"127.0.0.1:{base_port + i}"
        cmd = ["baikaldb_tpu.server.store_server", "--store-id", str(i),
               "--address", addr, "--meta", meta_addr]
        if aot_dir:
            cmd += ["--aot-dir", os.path.join(aot_dir, f"store{i}")]
        if cold_dir:
            cmd += ["--cold-dir", os.path.join(cold_dir, f"store{i}")]
        procs["stores"].append(_spawn(cmd, _DAEMON_ENV))
        _wait_ping(addr)
    if mysql_port and n_mysql > 0:
        for j in range(n_mysql):
            procs["mysqls"].append(_spawn(["baikaldb_tpu.server",
                                           "--port", str(mysql_port + j),
                                           "--meta", meta_addr]))
        procs["mysql"] = procs["mysqls"][0]
    return meta_addr, procs


def teardown(procs: dict) -> None:
    victims = [procs.get("meta")] + procs.get("mysqls", []) + \
        procs.get("stores", [])
    if procs.get("mysql") is not None and \
            procs["mysql"] not in procs.get("mysqls", []):
        victims.append(procs["mysql"])
    for p in victims:
        if p is not None and p.poll() is None:
            p.terminate()
    for p in victims:
        if p is not None:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--stores", type=int, default=3)
    ap.add_argument("--base-port", type=int, default=9100)
    ap.add_argument("--mysql-port", type=int, default=28000)
    ap.add_argument("--frontends", type=int, default=1,
                    help="MySQL frontends on consecutive ports")
    ap.add_argument("--aot-dir", default="",
                    help="fragment/AOT blob root (per-store subdirs)")
    ap.add_argument("--cold-dir", default="",
                    help="cold-segment FS root (per-store subdirs)")
    args = ap.parse_args()
    meta_addr, procs = spawn_cluster(args.stores, args.base_port,
                                     args.mysql_port,
                                     n_mysql=args.frontends,
                                     aot_dir=args.aot_dir,
                                     cold_dir=args.cold_dir)
    print(f"meta     @ {meta_addr} (pid {procs['meta'].pid})")
    for i, p in enumerate(procs["stores"], 1):
        print(f"store {i}  @ 127.0.0.1:{args.base_port + i} (pid {p.pid})")
    for j, p in enumerate(procs["mysqls"][1:], 1):
        print(f"mysql+{j}  @ 127.0.0.1:{args.mysql_port + j} (pid {p.pid})")
    if procs["mysql"] is not None:
        print(f"mysql    @ 127.0.0.1:{args.mysql_port} "
              f"(pid {procs['mysql'].pid})")
    print("cluster up — Ctrl-C to tear down", flush=True)

    def _stop(signum, frame):
        teardown(procs)
        sys.exit(0)

    signal.signal(signal.SIGINT, _stop)
    signal.signal(signal.SIGTERM, _stop)
    while True:
        time.sleep(3600)


if __name__ == "__main__":
    main()
