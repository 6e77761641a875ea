"""CLI entry: ``python -m minio_tpu.server [--address HOST:PORT] DIR...``
— the analogue of ``minio server`` (reference cmd/server-main.go:404).
Disk args may use ellipses patterns (``/data/disk{1...8}``, expanded by
minio_tpu.dist.ellipses) and are grouped into erasure sets of 4-16
drives. ``http://host:port/path`` endpoint args select DISTRIBUTED mode:
every process gets the same full endpoint list, serves the disks whose
URL matches its --address, and reaches the rest over storage RPC
(reference dist-erasure startup; buildscripts/verify-healing.sh drives
it the same way). ``--gateway nas|s3`` serves the S3 API over a backend.
Root credentials: MINIO_TPU_ROOT_USER/_PASSWORD (MINIO_ROOT_USER/
_PASSWORD also honored; default minioadmin/minioadmin)."""
from __future__ import annotations

import argparse
import os
import sys


def _root_creds() -> tuple[str, str]:
    ak = os.environ.get("MINIO_TPU_ROOT_USER") \
        or os.environ.get("MINIO_ROOT_USER") or "minioadmin"
    sk = os.environ.get("MINIO_TPU_ROOT_PASSWORD") \
        or os.environ.get("MINIO_ROOT_PASSWORD") or "minioadmin"
    return ak, sk


def arg_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="minio-tpu server")
    ap.add_argument("dirs", nargs="+", help="disk directories or "
                    "ellipses patterns like /data/disk{1...8}; "
                    "http://host:port/path endpoints = distributed mode")
    ap.add_argument("--address", default="0.0.0.0:9000",
                    help="host:port to listen on; comma-separate for "
                         "additional bindings (multi-addr listener)")
    ap.add_argument("--region", default="us-east-1")
    ap.add_argument("--parity", type=int, default=None,
                    help="parity drives per set (default: drives/2)")
    ap.add_argument("--gateway",
                    choices=["nas", "s3", "hdfs", "azure", "gcs"],
                    default=None,
                    help="gateway mode: serve the S3 API over a backend "
                         "(nas: shared mount path; s3: upstream endpoint)")
    return ap


def main(argv=None):
    ap = arg_parser()
    args = ap.parse_args(argv)
    if any(d.startswith(("http://", "https://")) for d in args.dirs) \
            and not args.gateway:
        if "," in args.address:
            ap.error("multi-addr --address is not supported in "
                     "distributed mode; pass the single URL this node "
                     "serves")
        return _serve_distributed(args, *_root_creds())
    srv, banner = build_server(args, ap)
    print(f"{banner}; listening on {args.address}", file=sys.stderr)
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass


def build_server(args, ap: argparse.ArgumentParser | None = None):
    """Everything a single-node (or gateway) launch does short of
    serving: object layer from the disk args, S3Server, service hook,
    background services started. Returns (server, banner) —
    ``main`` then serves forever; an embedding caller (chip_smoke.py)
    serves on a thread and shuts down through ``server.shutdown()``.
    ``args`` is ``arg_parser().parse_args([...])``."""
    ap = ap or arg_parser()
    ak, sk = _root_creds()
    if args.gateway:
        from ..gateway import new_gateway_layer
        if len(args.dirs) != 1:
            ap.error("gateway mode takes exactly one target")
        up_ak = os.environ.get("MINIO_TPU_GATEWAY_ACCESS_KEY", ak)
        up_sk = os.environ.get("MINIO_TPU_GATEWAY_SECRET_KEY", sk)
        obj = new_gateway_layer(args.gateway, args.dirs[0], up_ak, up_sk,
                                args.region)
        banner = f"gateway {args.gateway} -> {args.dirs[0]}"
    elif len(args.dirs) > 1 and any("{" in d for d in args.dirs) and \
            not all("{" in d for d in args.dirs):
        # the reference rejects mixed ellipses/non-ellipses endpoint args
        # (cmd/endpoint-ellipses.go): silently flattening `/p/d{1...4}
        # /extra` into one set layout would place data on a topology the
        # operator never asked for
        ap.error("invalid endpoint args: all disk args must use ellipses "
                 "patterns ({...}) or none may; mixing patterns and "
                 "plain paths is not supported")
    elif len(args.dirs) > 1 and all("{" in d for d in args.dirs):
        # multiple ellipses args = one POOL per arg (reference server
        # pool expansion: `minio server dir{1...4} dir{5...8}` is two
        # pools, cmd/endpoint-ellipses.go / erasure-server-pool.go)
        from ..dist.ellipses import expand_endpoints
        from ..dist.topology import pick_set_layout
        from ..objectlayer import ErasureSets, ServerPools
        from ..storage import XLStorage
        pools = []
        for spec in args.dirs:
            dirs = expand_endpoints([spec])
            set_count, per_set = pick_set_layout(len(dirs))
            pools.append(ErasureSets([XLStorage(d) for d in dirs],
                                     set_count, per_set,
                                     default_parity=args.parity))
        obj = ServerPools(pools)
        banner = f"erasure: {len(pools)} pools"
    else:
        from ..dist.ellipses import expand_endpoints
        dirs = expand_endpoints(args.dirs)

        from ..dist.topology import pick_set_layout
        from ..objectlayer import ErasureObjects, ErasureSets
        from ..storage import XLStorage
        disks = [XLStorage(d) for d in dirs]
        if len(disks) == 1:
            from ..fs import FSObjects
            obj = FSObjects(dirs[0])
            banner = f"FS mode on {dirs[0]}"
        else:
            set_count, per_set = pick_set_layout(len(disks))
            if set_count == 1:
                obj = ErasureObjects(disks, default_parity=args.parity)
            else:
                obj = ErasureSets(disks, set_count, per_set,
                                  default_parity=args.parity)
            banner = f"erasure: {set_count} set(s) x {per_set} drives"

    addrs = args.address.split(",")
    parsed = []
    for a in addrs:
        h, _, p = a.rpartition(":")
        try:
            parsed.append((h or "0.0.0.0", int(p)))
        except ValueError:
            ap.error(f"invalid --address entry {a!r} "
                     "(expected host:port)")
    (host, port), extra = parsed[0], parsed[1:]
    from . import S3Server
    srv = S3Server(obj, host or "0.0.0.0", int(port), args.region,
                   access_key=ak, secret_key=sk, extra_addresses=extra)
    if extra:
        banner += f"; +{len(extra)} extra listener(s)"
    if os.environ.get("MINIO_TPU_ETCD_ENDPOINTS"):
        # resolve the advertise address only when federation is actually
        # configured — gethostbyname can fail on minimal containers
        from ..dist.federation import federation_from_env
        import socket as _socket
        adv = host if host not in ("", "0.0.0.0") else \
            _socket.gethostbyname(_socket.gethostname())
        fed = federation_from_env(adv, int(port))
        if fed is not None:
            srv.enable_federation(fed)
            banner += f"; federated via etcd (domain {fed.domain})"
    _install_service_hook(srv)
    if not args.gateway:
        # background plane (scanner / MRF / auto-heal) runs on real
        # object layers; gateways proxy a backend that owns its own
        # durability (the reference skips these in gateway mode too)
        srv.start_background_services()
    return srv, banner


def _install_service_hook(srv) -> None:
    """mc admin service restart/stop (reference cmd/service.go: restart
    re-execs the same argv so config/env changes load; stop exits
    cleanly). Installed for every CLI mode — single node, gateway AND
    distributed — so the admin endpoint acts instead of silently
    acking."""
    def service_signal(action: str):
        if action == "restart":
            os.execv(sys.executable, [sys.executable, "-m",
                                      "minio_tpu.server",
                                      *sys.argv[1:]])
        os._exit(0)

    srv.on_service_signal = service_signal


def _serve_distributed(args, ak: str, sk: str):
    """Distributed startup: build the Node from the full endpoint list,
    identify ourselves by --address, serve until killed."""
    import socket
    import threading

    from ..dist.node import Node
    host, _, port = args.address.rpartition(":")
    host = host or "0.0.0.0"

    def build(local_url: str) -> Node:
        return Node(args.dirs, local_url=local_url, address=host,
                    port=int(port), access_key=ak, secret_key=sk,
                    region=args.region, default_parity=args.parity)

    node = build(f"http://{host}:{port}")
    if not node.local_disks:
        node = build(f"https://{host}:{port}")
    if not node.local_disks:
        # --address 0.0.0.0 (or a host alias) matches no endpoint URL;
        # retry with any endpoint on our port whose host resolves to a
        # local interface — silently owning zero disks makes a cluster
        # that comes up dead
        local_names = {"127.0.0.1", "localhost", socket.gethostname(),
                       socket.getfqdn()}
        candidates = {e.url for e in node.endpoints
                      if e.url and e.url.rsplit(":", 1)[-1] == port
                      and e.url.split("//", 1)[-1].rsplit(":", 1)[0]
                      in local_names}
        if len(candidates) == 1:
            node = build(candidates.pop())
    if not node.local_disks:
        sys.exit(f"error: --address {args.address} matches no endpoint "
                 f"URL; pass the URL this node serves (endpoints: "
                 f"{sorted({str(e.url) for e in node.endpoints})})")
    node.start()
    if getattr(node, "server", None) is not None:
        _install_service_hook(node.server)
    print(f"distributed node listening on {args.address} "
          f"({len(node.endpoints)} endpoints)", file=sys.stderr)
    try:
        threading.Event().wait()  # serve until SIGTERM/SIGINT
    except KeyboardInterrupt:
        pass
    node.shutdown()


if __name__ == "__main__":
    main()
