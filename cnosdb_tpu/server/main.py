"""`cnosdb-tpu` server entry point (reference: main/src/main.rs `cnosdb run`).

The HTTP/SQL service is attached here as the service layer lands; this
module always exists so the console script resolves.
"""
from __future__ import annotations

import argparse
import sys


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="cnosdb-tpu", description=__doc__)
    sub = p.add_subparsers(dest="cmd")
    run = sub.add_parser("run", help="run a data/query/meta node")
    run.add_argument("--config", default=None, help="TOML config path")
    run.add_argument("--data-dir", default="./cnosdb-data")
    run.add_argument("--http-port", type=int, default=8902)
    run.add_argument("-M", "--mode", default="singleton",
                     choices=["singleton", "query_tskv", "tskv", "query",
                              "meta"])
    run.add_argument("--meta", default=None,
                     help="meta service address host:port (cluster modes)")
    run.add_argument("--node-id", type=int, default=1)
    run.add_argument("--rpc-port", type=int, default=0,
                     help="node-to-node RPC port (0 = ephemeral)")
    run.add_argument("--meta-port", type=int, default=8901,
                     help="meta service port (mode=meta)")
    run.add_argument("--meta-peers", default=None,
                     help="replicated meta group members as "
                          "'1@host:port,2@host:port,...' (mode=meta)")
    run.add_argument("--meta-host", default="127.0.0.1",
                     help="meta RPC bind host; set 0.0.0.0 for multi-host "
                          "groups (the RPC plane is unauthenticated)")
    cfg = sub.add_parser("config", help="print default config")
    check = sub.add_parser("check", help="validate a config file")
    check.add_argument("path")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_arg_parser().parse_args(argv)
    if args.cmd in (None, "run"):
        from .http import run_server

        return run_server(args)
    if args.cmd == "config":
        from ..config import Config

        print(Config().to_toml())
        return 0
    if args.cmd == "check":
        from ..config import Config

        Config.load(args.path)
        print("config ok")
        return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
