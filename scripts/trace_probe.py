"""What the program's own tracing shows of a benchmark cell, and what it
costs — two uses of ``benchmark/run.py``'s parts in one process:

``python scripts/trace_probe.py dump OUT.json -- --workload <cell> --seed
<n> --seconds <s> --trace <0|1>`` runs the cell as ``benchmark/run.py``
does (same result line) and then writes what ``tracing.RING`` holds of
the window's statements to OUT.json: each root with its counters
(``host_sync_by_why``, ``lowerings_by_program``, ``plan_fp``), its
``exchange`` spans, its ``task`` spans (fragment, task, device, what it
waited for an exchange) and the operators' spans under them.

``python scripts/trace_probe.py onoff --workload <cell> --seed <n>
--seconds <s> --windows on,off,off,on,on,off`` sets the cell up once
(warm-up under both settings) and then runs one window a word, with
``query_tracing_enabled`` true (``on``) or false (``off``), printing
each window's end-to-end metrics: tracing on against off on the same
statements in the same process.  It uses only what ``benchmark/run.py``
had before the ``exchange`` span came, so it runs on an older checkout
too (every window ``on``: that checkout's level).

Both need the chips the cell asks for; ``--rehearse-cpu`` as in
``benchmark/run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402


def ring_statements(since: float = 0.0) -> list:
    """The ring's statement trees, cut to what the distributed runner
    says of a statement: the root, the ``exchange`` and ``task`` spans
    and the operators' spans (``parent`` is the task's ``span_id``)."""
    from trino_tpu.telemetry import tracing

    traces, lost = tracing.RING.since(since)
    out = []
    for spans in traces:
        root = next((s for s in spans if s["parent_id"] is None), None)
        if root is None or root["name"] != "statement":
            continue
        kept = [s for s in spans if s["name"] in ("exchange", "task")
                or s["attrs"].get("span_kind") == "operator"]
        out.append({"t0": root["t0"], "t1": root["t1"],
                    "root": root["attrs"],
                    "spans": [{"name": s["name"], "t0": s["t0"],
                               "t1": s["t1"], "id": s["span_id"],
                               "parent": s["parent_id"],
                               "attrs": s["attrs"]}
                              for s in sorted(kept, key=lambda s: s["t0"])]})
    return [{"lost": lost}] + out


def dump(out_path: str, argv: list) -> int:
    code = run.main(argv)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(ring_statements(), f)
    return code


def onoff(args) -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, = [w for w in bench["workloads"] if w["name"] == args.workload]
    entry, = [c for c in bench["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    from benchmark.traffic import (build_pool, check_traffic, load_json,
                                   stream_sequence)

    traffic = load_json("traffic", cell["traffic"] + ".json")
    check_traffic(traffic)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.COMPILE_CACHE
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        run.force_cpu_devices(cell["chips"])
    import jax

    import trino_tpu  # noqa: F401  (x64 on before any array)
    from trino_tpu.compile_cache import enable_compile_cache

    from benchmark.references.hosttables import HostTables

    platform = jax.devices()[0].platform
    if (platform == "tpu") == bool(args.rehearse_cpu):
        sys.exit(f"trace_probe.py: platform {platform!r} "
                 f"with rehearse_cpu={args.rehearse_cpu}")
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_max_size", -1)
    counter = run.CompileCounter()
    pool = build_pool(traffic, args.seed)
    tables = HostTables(config["schema"])
    rows_read = {inst.template.name: sum(
        tables.row_count(name) for name in inst.template.tables)
        for inst in pool}
    calls: list = []
    server = run.build_system(config, calls)
    properties = server.runner.session.properties
    words = args.windows.split(",")
    try:
        for word in sorted(set(words)):     # both plan-cache keys warm
            properties["query_tracing_enabled"] = word == "on"
            passes = run.warm_up(server.uri, pool, traffic, args.seed,
                                 counter)
            run.say(phase="warm_up", tracing=word, passes=passes)
        setup_s = time.perf_counter() - run.T_START
        for word in words:
            properties["query_tracing_enabled"] = word == "on"
            facts = run.RunFacts(rows_read=rows_read, setup_s=setup_s)
            compiles0 = counter.requests
            facts.window_open = time.perf_counter()
            facts.statements = run.drive(
                server.uri,
                [stream_sequence(pool, args.seed, s)
                 for s in range(traffic["streams"])],
                stop_after=facts.window_open + args.seconds)
            facts.window_close = max(s.t_done for s in facts.statements)
            report = run.check(facts.statements, tables)
            run.say(window=word, seed=args.seed,
                    statements=len(facts.statements),
                    failed=len(facts.statements) - len(facts.finished),
                    mismatched=sum(r["mismatched_values"]
                                   for r in report.values()),
                    seconds=[round(s.seconds, 4)
                             for s in facts.statements],
                    compiles=counter.requests - compiles0,
                    metrics=run.read_metrics(
                        "end_to_end", bench["end_to_end"], cell, facts))
    finally:
        server.stop()
    if args.dump:
        with open(args.dump, "w") as f:
            json.dump(ring_statements(), f)
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["dump"]:
        return dump(argv[1], argv[argv.index("--") + 1:])
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("onoff",))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", default="on,off,off,on,on,off")
    ap.add_argument("--dump", help="write the ring's statements here")
    ap.add_argument("--rehearse-cpu", action="store_true")
    return onoff(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
