"""Command-line entry point — the reference's single ``gb`` binary.

Reference: ``main.cpp:395`` (``main2``) parses a command verb and either
runs a node (HTTP server + spider + autosave event loop) or performs a
one-shot operation (``main.cpp:1084-3887``: ``gb inject``, ``gb dump``,
``gb save``, ``gb spider`` …). Same shape here::

    python -m open_source_search_engine_tpu serve  --dir ./data --port 8000
    python -m open_source_search_engine_tpu inject --dir ./data URL [FILE]
    python -m open_source_search_engine_tpu search --dir ./data "query"
    python -m open_source_search_engine_tpu crawl  --dir ./data --seeds U
    python -m open_source_search_engine_tpu save   --dir ./data

``serve`` is the long-running node: collections + HTTP API + autosave +
orderly signal shutdown (``Process.cpp:1299`` autosave clock,
``Process.cpp:1595`` save-on-signal). Everything else is a one-shot verb
against the same on-disk state — a restart is lossless (Rdb runs +
memtable ``saved/`` checkpoints).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def _add_dir(p: argparse.ArgumentParser) -> None:
    p.add_argument("--dir", default="./osse_data",
                   help="node data directory (default ./osse_data)")
    p.add_argument("--coll", default="main",
                   help="collection name (default main)")


def cmd_serve(args) -> int:
    from .control.process import Process
    from .serve.server import SearchHTTPServer
    from .spider.loop import SpiderLoop
    from .utils import compilecache

    compilecache.configure()

    cluster = None
    if args.hosts:
        from .parallel.cluster import ClusterClient, HostsConf
        cluster = ClusterClient(HostsConf.load(args.hosts))
        if args.spider:
            print("--spider is ignored with --hosts: crawled pages "
                  "would land in the local collection while searches "
                  "go to the cluster", file=sys.stderr)
            args.spider = False
    srv = SearchHTTPServer(args.dir, host=args.host, port=args.port,
                           cluster=cluster)
    coll = srv.colldb.get(args.coll)
    spider = SpiderLoop(coll)
    srv.spider = spider
    proc = Process(autosave_minutes=args.autosave)
    proc.register(srv.colldb)
    proc.install_signal_handlers()
    proc.start_autosave()
    # quiet-hours full merges (DailyMerge.h:11); window from the
    # merge_quiet_hours parm, empty = disabled
    from .control.dailymerge import DailyMerge
    dm = DailyMerge(lambda: [srv.colldb.get(n)
                             for n in srv.colldb.names()], srv.conf)
    dm.start()
    proc.on_shutdown(dm.stop)
    srv.start()
    print(f"node serving on http://{args.host}:{srv.port} "
          f"(coll={args.coll}, dir={args.dir}) — Ctrl-C to save+stop",
          flush=True)
    try:
        while not proc.stopping:
            if args.spider:
                n = spider.crawl_step()
                if n == 0:
                    time.sleep(1.0)
            else:
                time.sleep(0.5)
    except KeyboardInterrupt:
        pass
    proc.shutdown()
    srv.stop()
    return 0


def cmd_proxy(args) -> int:
    """Query-routing front proxy (the ``gb proxy`` mode,
    ``main.cpp:1691`` / ``Proxy.cpp``): a stateless front end that fans
    /search out to the cluster's nodes and serves merged results — no
    local index, no spider; run several behind a load balancer."""
    import tempfile

    from .parallel.cluster import ClusterClient, HostsConf
    from .serve.server import SearchHTTPServer

    cluster = ClusterClient(HostsConf.load(args.hosts))
    srv = SearchHTTPServer(tempfile.mkdtemp(prefix="osse_proxy_"),
                           host=args.host, port=args.port,
                           cluster=cluster)
    srv.start()
    print(f"proxy on http://{args.host}:{srv.port} "
          f"-> cluster {args.hosts} — Ctrl-C to stop", flush=True)
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        pass
    srv.stop()
    cluster.close()
    return 0


def cmd_inject(args) -> int:
    from .build import docproc
    from .index.collection import CollectionDb

    colldb = CollectionDb(args.dir)
    coll = colldb.get(args.coll)
    content = (Path(args.file).read_text(encoding="utf-8", errors="replace")
               if args.file else sys.stdin.read())
    ml = docproc.index_document(coll, args.url, content)
    colldb.save_all()
    if ml is None:
        print(json.dumps({"injected": args.url, "error": "banned"}))
        return 1
    print(json.dumps({"injected": args.url, "docid": int(ml.docid),
                      "docs": coll.num_docs}))
    return 0


def cmd_search(args) -> int:
    from .index.collection import CollectionDb
    from .query import engine

    coll = CollectionDb(args.dir).get(args.coll, create=False)
    if args.device:
        from .utils import compilecache
        compilecache.configure()
    search = engine.search_device if args.device else engine.search
    res = search(coll, args.query, topk=args.k)
    out = {
        "query": res.query,
        "total": res.total_matches,
        "degraded": res.degraded,
        "results": [{"url": r.url, "title": r.title,
                     "score": round(r.score, 3), "docid": r.docid,
                     "snippet": r.snippet} for r in res.results],
    }
    if res.suggestion:
        out["suggestion"] = res.suggestion
    print(json.dumps(out, indent=None if args.json else 2))
    return 0


def cmd_crawl(args) -> int:
    from .index.collection import CollectionDb
    from .spider.loop import SpiderLoop

    from .spider.spiderdb import DurableSpiderScheduler

    colldb = CollectionDb(args.dir)
    coll = colldb.get(args.coll)
    sched = DurableSpiderScheduler(
        Path(args.dir) / "spider" / args.coll,
        banned=coll.tagdb.is_banned)
    loop = SpiderLoop(coll, scheduler=sched)
    for seed in (args.seeds or "").split(","):
        if seed.strip():
            loop.add_url(seed.strip())
    stats = loop.crawl(max_pages=args.max_pages)
    colldb.save_all()
    loop.sched.save()
    print(json.dumps({"fetched": stats.fetched, "indexed": stats.indexed,
                      "errors": stats.errors, "docs": coll.num_docs}))
    return 0


def cmd_node(args) -> int:
    """Run one shard-replica node process (the cluster's unit — the
    reference's per-host gb instance; RPC surface in parallel.cluster).
    A fleet supervisor spawns this verb once per (shard, replica) with
    the serialized cluster map (`--hosts`), the node's seat in it, and
    the chaos seed in OSSE_CHAOS — the child arms its own seams so a
    cross-process fault schedule replays deterministically."""
    import os
    import signal

    from .parallel.cluster import HostsConf, ShardNodeServer
    from .utils import chaos as chaos_mod

    chaos_mod.maybe_enable()
    cluster_map = HostsConf.load(args.hosts) if args.hosts else None
    node = ShardNodeServer(args.dir, host=args.host, port=args.port,
                           use_device=args.device, shard=args.shard,
                           replica=args.replica,
                           cluster_map=cluster_map)
    node.start()
    print(json.dumps({"node": f"{args.host}:{node.port}",
                      "docs": node.coll.num_docs,
                      "shard": args.shard, "replica": args.replica,
                      "pid": os.getpid()}), flush=True)
    stop = [False]

    def handler(signum, frame):
        stop[0] = True  # save happens below, under the writer lock

    signal.signal(signal.SIGTERM, handler)
    signal.signal(signal.SIGINT, handler)
    while not stop[0]:
        time.sleep(0.3)
    node.save()
    node.stop()
    return 0


def cmd_save(args) -> int:
    from .index.collection import CollectionDb

    colldb = CollectionDb(args.dir)
    for name in colldb.names():
        colldb.get(name)
    colldb.save_all()
    print(json.dumps({"saved": colldb.names() or [args.coll]}))
    return 0


def cmd_rebalance(args) -> int:
    """gb scale (main.cpp:2356): grow/shrink the shard grid."""
    from .control.rebalance import rebalance

    dst = rebalance(args.coll, args.dir, args.out,
                    old_n_shards=args.old_shards,
                    new_n_shards=args.new_shards,
                    n_replicas=args.replicas)
    print(json.dumps({"shards": dst.n_shards, "docs": dst.num_docs,
                      "out": args.out}))
    return 0


def cmd_repair(args) -> int:
    """Repair.h rebuild: posdb/clusterdb/linkdb from titledb."""
    from .control.rebalance import repair
    from .index.collection import CollectionDb

    colldb = CollectionDb(args.dir)
    coll = colldb.get(args.coll, create=False)
    n = repair(coll)
    print(json.dumps({"repaired": args.coll, "docs": n}))
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m open_source_search_engine_tpu",
        description="TPU-native search engine node (the gb binary, "
                    "reference main.cpp)")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("serve", help="run a node: HTTP API + autosave")
    _add_dir(p)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--autosave", type=float, default=5.0,
                   help="autosave interval, minutes")
    p.add_argument("--spider", action="store_true",
                   help="also run the crawl loop in-process")
    p.add_argument("--hosts", help="hosts.conf: front a node cluster "
                   "instead of a local collection")
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser("inject", help="index one document")
    _add_dir(p)
    p.add_argument("url")
    p.add_argument("file", nargs="?", help="HTML file (default: stdin)")
    p.set_defaults(fn=cmd_inject)

    p = sub.add_parser("search", help="query a collection")
    _add_dir(p)
    p.add_argument("query")
    p.add_argument("-k", type=int, default=10)
    p.add_argument("--device", action="store_true",
                   help="use the HBM-resident index path")
    p.add_argument("--json", action="store_true", help="compact JSON")
    p.set_defaults(fn=cmd_search)

    p = sub.add_parser("crawl", help="run the spider")
    _add_dir(p)
    p.add_argument("--seeds", help="comma-separated seed URLs")
    p.add_argument("--max-pages", type=int, default=100)
    p.set_defaults(fn=cmd_crawl)

    p = sub.add_parser("node", help="run one shard-replica node (cluster)")
    p.add_argument("--dir", default="./osse_shard")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--device", action="store_true",
                   help="serve queries from the HBM-resident index")
    p.add_argument("--hosts", help="hosts.conf cluster map handed out "
                   "at spawn (Hostdb: every instance boots knowing "
                   "the topology)")
    p.add_argument("--shard", type=int, default=0,
                   help="this node's shard id in the map")
    p.add_argument("--replica", type=int, default=0,
                   help="this node's twin id within the shard")
    p.set_defaults(fn=cmd_node)

    p = sub.add_parser("proxy", help="query-routing front proxy "
                                     "(gb proxy mode): /search fans "
                                     "out to the cluster, no local "
                                     "index")
    p.add_argument("--hosts", required=True,
                   help="hosts.conf cluster topology")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.set_defaults(fn=cmd_proxy)

    p = sub.add_parser("save", help="checkpoint all collections")
    _add_dir(p)
    p.set_defaults(fn=cmd_save)

    p = sub.add_parser("rebalance",
                       help="re-shard a collection grid (gb scale)")
    _add_dir(p)
    p.add_argument("--out", required=True, help="new grid directory")
    p.add_argument("--old-shards", type=int, required=True)
    p.add_argument("--new-shards", type=int, required=True)
    p.add_argument("--replicas", type=int, default=1)
    p.set_defaults(fn=cmd_rebalance)

    p = sub.add_parser("repair",
                       help="rebuild index Rdbs from titledb")
    _add_dir(p)
    p.set_defaults(fn=cmd_repair)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
