"""`python -m baikaldb_tpu.server` — the `baikaldb` frontend binary analog
(reference: src/protocol/main.cpp startup sequence)."""

import argparse
import time


def main():
    ap = argparse.ArgumentParser(description="baikaldb_tpu MySQL-protocol server")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=28000)
    ap.add_argument("--qos-rate", type=float, default=0.0,
                    help="global queries/sec admission limit (0 = off)")
    ap.add_argument("--meta", default="",
                    help="meta daemon host:port — DML replicates to the "
                         "store daemon cluster it places")
    ap.add_argument("--data-dir", default="",
                    help="durable single-node mode (WAL + Parquet)")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="row-shard every table over a mesh of this many "
                         "devices and run SELECTs as one program over it "
                         "(SET GLOBAL mesh_devices; 0 = one device)")
    args = ap.parse_args()

    from ..exec.session import Database
    from ..utils.flags import set_flag
    from .mysql_server import MySQLServer

    qos = None
    if args.qos_rate > 0:
        from ..utils.qos import QosManager

        qos = QosManager(global_rate=args.qos_rate,
                         global_burst=2 * args.qos_rate,
                         sign_rate=args.qos_rate / 4,
                         sign_burst=args.qos_rate / 2)
    db = Database(data_dir=args.data_dir or None,
                  cluster=args.meta or None)
    if args.mesh_devices:
        set_flag("mesh_devices", args.mesh_devices)
        db.mesh     # more devices than the process has: fail at start
    srv = MySQLServer(db, host=args.host, port=args.port, qos=qos).start()
    print(f"baikaldb_tpu listening on {args.host}:{srv.port}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()


if __name__ == "__main__":
    main()
