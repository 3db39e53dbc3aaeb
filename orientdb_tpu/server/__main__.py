"""Server process entry point ([E] OServerMain / server.sh).

    python -m orientdb_tpu.server [--http-port N] [--binary-port N]
        [--admin-password PW] [--db NAME ...] [--demodb]

Ports default to ephemeral (printed on startup). With wal_enabled +
wal_dir configured (ORIENTTPU_WAL_ENABLED / ORIENTTPU_WAL_DIR), named
databases recover-or-create durably.
"""

from __future__ import annotations

import argparse
import signal
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="orientdb-tpu-server")
    ap.add_argument("--http-port", type=int, default=0)
    ap.add_argument("--binary-port", type=int, default=0)
    ap.add_argument("--admin-password", default="admin")
    ap.add_argument("--db", action="append", default=[], help="create/open a named database")
    ap.add_argument("--demodb", action="store_true", help="bundle the demodb sample database")
    args = ap.parse_args(argv)

    from orientdb_tpu.server.server import Server
    from orientdb_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    srv = Server(
        admin_password=args.admin_password,
        http_port=args.http_port,
        binary_port=args.binary_port,
    )
    for name in args.db:
        srv.create_database(name)
    if args.demodb:
        from orientdb_tpu.storage.ingest import generate_demodb
        from orientdb_tpu.storage.snapshot import attach_fresh_snapshot

        db = srv.create_database("demodb")
        if not db.schema.exists_class("Profiles"):
            # a durable demodb recovers from disk — don't regenerate
            generate_demodb(db)
        attach_fresh_snapshot(db)
    srv.startup()
    print(
        f"orientdb-tpu server up: http={srv.http_port} binary={srv.binary_port}",
        flush=True,
    )
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    try:
        while not stop:
            signal.pause()
    finally:
        srv.shutdown()
    return 0


if __name__ == "__main__":  # pragma: no cover - process entry
    sys.exit(main())
