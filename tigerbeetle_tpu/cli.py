"""CLI: format / start / version / repl / benchmark subcommands.

Mirrors the reference's command surface (src/tigerbeetle/main.zig:41-67,
cli.zig:17-74): `format` initializes a data file, `start` serves it over TCP,
`repl` talks to a running cluster, `benchmark` measures create_transfers
throughput (spawning a temp single-replica cluster if no --addresses given,
benchmark_driver.zig:50-64).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
from typing import List, Tuple

import numpy as np


def _parse_addresses(value: str) -> List[Tuple[str, int]]:
    out = []
    for part in value.split(","):
        host, _, port = part.rpartition(":")
        out.append((host or "127.0.0.1", int(port)))
    return out


def _statsd_addr(value: str) -> Tuple[str, int]:
    """argparse type for --statsd: HOST:PORT with a real port.

    A malformed value used to surface as an unhandled ValueError traceback
    from deep inside _parse_addresses; argparse.ArgumentTypeError turns it
    into the standard two-line usage error instead."""
    host, _, port = value.rpartition(":")
    if not port or not port.isdigit():
        raise argparse.ArgumentTypeError(
            f"expected HOST:PORT with a numeric port, got {value!r}"
        )
    port_n = int(port)
    if not 0 < port_n < 65536:
        raise argparse.ArgumentTypeError(
            f"port {port_n} out of range 1-65535"
        )
    return (host or "127.0.0.1", port_n)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="tigerbeetle-tpu",
        description="TPU-native accounting database (TigerBeetle-compatible wire protocol)",
    )
    from .config import PROCESS_DEFAULT

    default_address = f"{PROCESS_DEFAULT.address}:{PROCESS_DEFAULT.port}"
    sub = parser.add_subparsers(dest="subcommand", required=True)

    p_format = sub.add_parser("format", help="initialize a replica data file")
    p_format.add_argument("path")
    p_format.add_argument("--cluster", type=lambda s: int(s, 0), required=True)
    p_format.add_argument("--replica", type=int, default=0)
    p_format.add_argument("--replica-count", type=int, default=1)
    p_format.add_argument("--standby-count", type=int, default=0,
                          help="non-voting members that consume the prepare "
                               "stream (indexes replica_count..)")

    p_promote = sub.add_parser(
        "promote", help="promote a standby data file to a voting index"
    )
    p_promote.add_argument("path")
    p_promote.add_argument("--replica", type=int, required=True,
                           help="target voting index (the retired voter's)")

    p_start = sub.add_parser("start", help="serve a formatted data file")
    p_start.add_argument("path")
    p_start.add_argument("--addresses", default=default_address,
                         help="host:port to listen on")
    p_start.add_argument("--cache-accounts-log2", type=int, default=None,
                         metavar="N",
                         help="accounts table capacity at start, 2^N slots "
                              "(default 16; alone it also sets the "
                              "transfers table to N + 2)")
    p_start.add_argument("--cache-transfers-log2", type=int, default=None,
                         metavar="N",
                         help="transfers table capacity at start, 2^N "
                              "slots (default 18)")
    p_start.add_argument("--cache-posted-log2", type=int, default=None,
                         metavar="N",
                         help="posted table capacity at start, 2^N slots "
                              "(default 16): one row per posted or voided "
                              "pending transfer.  Every table still "
                              "doubles at load 0.5, and each growth "
                              "recompiles the commit kernels: size for "
                              "twice the rows you expect; under --shards "
                              "the posted table grows at load 0.25 (its "
                              "keys' owners are not known to the host): "
                              "size for four times the rows, one power of "
                              "two above the one-chip size")
    p_start.add_argument("--aof", default=None, metavar="PATH",
                         help="append-only audit log of committed prepares")
    p_start.add_argument("--statsd", default=None, metavar="HOST:PORT",
                         type=_statsd_addr,
                         help="emit StatsD metrics (UDP, best-effort)")
    p_start.add_argument("--metrics-json", default=None, metavar="PATH",
                         help="enable the metrics registry and dump a JSON "
                              "snapshot to PATH on shutdown (env twin: "
                              "TB_METRICS_PATH)")
    p_start.add_argument("--direct-io", action="store_true",
                         help="open the data file O_DIRECT (sector-aligned "
                              "IO; bypasses page-cache writeback)")
    p_start.add_argument("--direct-io-required", action="store_true",
                         help="refuse to start if the filesystem lacks "
                              "O_DIRECT instead of falling back")
    p_start.add_argument("--tick-ms", type=int, default=None,
                         help="cluster consensus tick cadence")
    p_start.add_argument("--hot-transfers-log2-max", type=int, default=None,
                         metavar="N",
                         help="cap the device-resident transfers window at "
                              "2^N slots; older transfers spill to a cold "
                              "host store (BASELINE config 4 tiering).  At "
                              "the cap an eviction moves the older half of "
                              "the window to an id-sorted run file beside "
                              "the data file, inline on the serving thread, "
                              "before the batch that would pass load 0.5 "
                              "(docs/deploy.md).  Start the table there: "
                              "--cache-transfers-log2 N")
    p_start.add_argument("--cold-bloom-log2", type=int, default=None,
                         metavar="N",
                         help="bits of the cold tier's Bloom filter, 2^N, "
                              "allocated at start with "
                              "--hot-transfers-log2-max and carried into "
                              "every commit (default: the hot window's log2 "
                              "+ 6, i.e. 12 bits an id for a cold store of "
                              "eight hot windows).  It never changes shape "
                              "under that design load; past it it grows, "
                              "and the commit program recompiles.  A false "
                              "positive costs one more dispatch of the "
                              "whole batch: size for a batch, not an id "
                              "(docs/deploy.md)")
    p_start.add_argument("--pipeline-depth", type=int, default=None,
                         metavar="N",
                         help="commit-pipeline depth for the serving path: "
                              "1 = fully blocking (the pre-pipeline "
                              "engine), >= 2 = deferred device readbacks "
                              "with one commit group in flight (deeper "
                              "values reserved, currently equivalent to "
                              "2; default 2; env twin: TB_PIPELINE, 0 = "
                              "off)")
    p_start.add_argument("--shards", type=int, default=None, metavar="N",
                         help="sharded execution mode (docs/sharding.md): "
                              "partition the device ledger over N devices "
                              "(power of two) and commit through shard_map "
                              "— account capacity scales with device count "
                              "and each shard is a commit lane.  0/absent "
                              "= single-device (bit-identical to pre-"
                              "sharding; env twin: TB_SHARDS).  Exclusive "
                              "with --hot-transfers-log2-max (cold tiering "
                              "is single-device)")
    p_start.add_argument("--overload-control", action="store_true",
                         help="explicit overload control (vsr/overload.py): "
                              "shed new requests with retryable busy "
                              "replies + retry-after hints instead of "
                              "silent drops, and shed the bounded send "
                              "queues by priority class so a client flood "
                              "never starves repair or an election (env "
                              "twin: TB_OVERLOAD; default off — the off "
                              "path is bit-identical)")
    p_start.add_argument("--scrub-interval", type=int, default=None,
                         metavar="N",
                         help="device fault domain (docs/fault_domains.md): "
                              "scrub the device-resident ledger against the "
                              "host mirror every N commit batches and at "
                              "every checkpoint boundary; enables dispatch "
                              "retry/quarantine and device-state recovery. "
                              "0 = off (default; env twin: "
                              "TB_SCRUB_INTERVAL)")
    p_start.add_argument("--merkle", action="store_true",
                         help="merkle commitment mode "
                              "(docs/commitments.md): the scrub substrate "
                              "becomes the on-device incremental Merkle "
                              "tree — root-compare checks with no host "
                              "mirror replay, replay-free verifiable "
                              "checkpoint roots, and client-verifiable "
                              "get_proof balance proofs (env twin: "
                              "TB_MERKLE; needs --scrub-interval >= 1; "
                              "forces the device commit path — the "
                              "forest commits to the device pads, which "
                              "the host engine does not maintain)")
    p_start.add_argument("--no-engine", action="store_true",
                         help="force the device-kernel commit path even "
                              "when the native host engine is available")
    p_start.add_argument("--engine", action="store_true",
                         help="multi-replica only: commit through the native "
                              "host engine (cluster replicas default to the "
                              "device path, which carries per-commit digests "
                              "and tiering)")

    p_version = sub.add_parser("version")
    p_version.add_argument("--verbose", action="store_true")

    p_repl = sub.add_parser("repl", help="interactive statement shell")
    p_repl.add_argument("--addresses", default=default_address)
    p_repl.add_argument("--cluster", type=lambda s: int(s, 0), required=True)
    p_repl.add_argument("--command", default=None,
                        help="one-shot statement(s); omit for interactive")

    p_vopr = sub.add_parser(
        "vopr", help="deterministic fault-injection simulator (the VOPR)"
    )
    p_vopr.add_argument("--seed", type=int, default=None,
                        help="single seed; omit for a random one")
    p_vopr.add_argument("--count", type=int, default=1,
                        help="number of consecutive seeds to run")
    p_vopr.add_argument("--ticks", type=int, default=None,
                        help="schedule ticks (default: 6000; the byzantine "
                             "kind defaults to 2600)")
    p_vopr.add_argument("--tpu", action="store_true",
                        help="run the vectorized protocol-model VOPR on "
                             "the available accelerator mesh instead")
    p_vopr.add_argument("--clusters", type=int, default=4096,
                        help="(--tpu) simulated clusters in the batch")
    p_vopr.add_argument("--steps", type=int, default=400)
    # Keep in sync with sim.vopr_tpu.BUGS (asserted in _cmd_vopr; a
    # module import here would pull jax into every CLI invocation).
    vopr_bugs = ["commit_quorum", "canonical_by_op", "no_truncate",
                 "corrupt_serve", "wal_wrap", "split_brain",
                 "amputate_vouch", "join_keep_stale", "scrub_off"]
    p_vopr.add_argument("--bug", default=None, choices=vopr_bugs,
                        help="(--tpu) inject a known consensus bug to "
                             "validate the oracle")
    p_vopr.add_argument("--vopr-viz", action="store_true",
                        help="record the one-line-per-event cluster status "
                             "grid; on a failing seed it is written to "
                             "vopr_viz_<seed>.txt and its tail printed "
                             "(env twin: TB_VOPR_VIZ)")
    p_vopr.add_argument("--metrics-json", default=None, metavar="PATH",
                        help="dump fault/outcome counters to PATH")
    p_vopr.add_argument("--device-faults", action="store_true",
                        help="inject the device fault kind (seeded SDC bit "
                             "flips into ledger columns + forced dispatch "
                             "exceptions) from a separate stream")
    p_vopr.add_argument("--scrub-interval", type=int, default=None,
                        metavar="N",
                        help="arm every replica's scrub mirror at cadence N "
                             "(0 = off; with --device-faults and N=0 the "
                             "run demonstrates the undetected-SDC failure)")
    p_vopr.add_argument("--merkle", action="store_true",
                        help="with --scrub-interval: merkle commitment "
                             "mode, mirror OFF — SDC must be detected by "
                             "root mismatch and recovered via checkpoint + "
                             "WAL replay (docs/commitments.md)")
    p_vopr.add_argument("--overload", action="store_true",
                        help="run the OVERLOAD fault kind instead of the "
                             "random schedule: seeded client flood at 2-8x "
                             "pipeline capacity with a mid-flood primary "
                             "crash; oracles: bounded memory + flood-proof "
                             "liveness (docs/fault_domains.md)")
    p_vopr.add_argument("--no-priority", action="store_true",
                        help="with --overload: force priority scheduling "
                             "OFF (bounded FIFO tail-drop) — the negative "
                             "control that demonstrably fails the "
                             "liveness oracle")
    p_vopr.add_argument("--byzantine", action="store_true",
                        help="run the BYZANTINE fault kind: one replica of "
                             "six equivocates prepares, corrupts bodies "
                             "under stale checksums, replays captured "
                             "frames, and forges lying client replies, "
                             "under the deterministic open-loop workload; "
                             "oracle: the auditor (docs/fault_domains.md)")
    p_vopr.add_argument("--no-verify", action="store_true",
                        help="with --byzantine: force checksum/source/"
                             "consensus ingress verification OFF — the "
                             "negative control that demonstrably fails "
                             "the safety oracle")
    p_vopr.add_argument("--primary-seat", action="store_true",
                        help="with --byzantine: seat 0 (the view-0 "
                             "PRIMARY) is the liar — equivocating "
                             "prepares and start_views plus fork-serving "
                             "headers; combine with --auth for the "
                             "defended run, --no-verify for the negative "
                             "control (docs/fault_domains.md)")
    p_vopr.add_argument("--auth", action="store_true",
                        help="with --byzantine: arm strict per-replica "
                             "wire MACs (vsr/auth.py) — authenticated "
                             "certificates are what contain the "
                             "primary-seat liar")
    p_vopr.add_argument("--catchup", action="store_true",
                        help="run the CATCH-UP scenario: crash one backup "
                             "mid-open-loop-flood in a merkle-armed "
                             "cluster, advance >= 2 checkpoints, heal — "
                             "the rejoiner must converge byte-identically "
                             "via Merkle-anchored incremental state sync "
                             "(docs/state_sync.md)")
    p_vopr.add_argument("--force-full", action="store_true",
                        help="with --catchup: pin the rejoiner to the "
                             "full-checkpoint transfer (the proven-"
                             "identical fallback control)")
    p_vopr.add_argument("--lying-responder", action="store_true",
                        help="with --catchup: the rejoiner's default "
                             "responder serves corrupted subtree rows "
                             "under valid checksums; root verification "
                             "must reject + rotate (add --no-verify for "
                             "the install-divergent-state negative "
                             "control)")
    p_vopr.add_argument("--reconfig", action="store_true",
                        help="run the RECONFIGURATION fault kind: online "
                             "2->4 shard split mid-open-loop-flood with a "
                             "crash of one migration source and a corrupt "
                             "chunk, plus a committed membership op "
                             "promoting the standby and a primary kill "
                             "(docs/reconfiguration.md; add --no-verify "
                             "for the install-divergent-state negative "
                             "control)")
    p_vopr.add_argument("--replay-schedule", default=None, metavar="FILE",
                        help="re-execute a tbmc counterexample schedule "
                             "(sim/mc.py, docs/tbmc.md) bit-identically "
                             "and verify the recorded violation + state "
                             "key reproduce; exclusive with every other "
                             "vopr knob (the schedule file pins scope, "
                             "mutations, and events)")

    p_bench = sub.add_parser("benchmark", help="client-driven load benchmark")
    p_bench.add_argument("--addresses", default=None,
                         help="existing cluster; omit to spawn a temp replica")
    p_bench.add_argument("--cluster", type=lambda s: int(s, 0), default=0)
    p_bench.add_argument("--account-count", type=int, default=10_000)
    p_bench.add_argument("--transfer-count", type=int, default=1_000_000)
    p_bench.add_argument("--transfer-batch-size", type=int, default=8190)

    args = parser.parse_args(argv)

    # Backend policy: the simulator, formatter, and repl are host/CPU work —
    # pin them to CPU.  The server and benchmark take the platform JAX gives
    # them (an accelerator that fails to initialize is an error, never a
    # silent CPU run) and say on stderr which one it is.
    from . import jaxenv

    if args.subcommand in ("format", "promote", "repl") or (
        args.subcommand == "vopr" and not args.tpu
    ):
        # The reconfiguration kind's 2 -> 4 online split shards across
        # 4 devices; every other CPU-pinned path is fine with one.
        jaxenv.force_cpu(8 if getattr(args, "reconfig", False) else None)
    elif (
        args.subcommand in ("start", "benchmark")
        or (args.subcommand == "vopr" and args.tpu)
        or (args.subcommand == "version" and args.verbose)
    ):
        if args.subcommand == "start":
            # Before the backend initializes: a served start must find the
            # kernels a previous start compiled.
            jaxenv.enable_compile_cache()
        args.backend = jaxenv.backend_info()
        if args.subcommand != "start":  # start adds its executor below
            _announce_backend(args.backend)

    return {
        "format": _cmd_format,
        "promote": _cmd_promote,
        "start": _cmd_start,
        "version": _cmd_version,
        "repl": _cmd_repl,
        "benchmark": _cmd_benchmark,
        "vopr": _cmd_vopr,
    }[args.subcommand](args)


def _announce_backend(backend, **extra) -> None:
    """The one stderr line that says where this process computes (stdout's
    first line stays ``listening host:port`` for the tools that parse it)."""
    platform, device_kind, count = backend
    print("device " + json.dumps({
        "platform": platform, "device_kind": device_kind, "count": count,
        **extra,
    }), file=sys.stderr, flush=True)


def _cmd_vopr(args) -> int:
    import secrets

    from .sim.vopr import EXIT_CORRECTNESS

    if args.replay_schedule is not None:
        # Loudly exclusive (the PR 5/6 flag discipline): the schedule
        # file pins the scope, mutations, and every event — any other
        # knob would silently describe a run that never happened.
        if (
            args.seed is not None or args.count != 1
            or args.ticks is not None or args.tpu
            or args.overload or args.no_priority
            or args.byzantine or args.no_verify
            or args.catchup or args.force_full or args.lying_responder
            or args.reconfig
            or args.device_faults or args.scrub_interval is not None
            or args.merkle or args.vopr_viz or args.bug is not None
            or args.clusters != 4096 or args.steps != 400
        ):
            print("error: --replay-schedule is exclusive with every other "
                  "vopr flag (the schedule file pins scope, mutations, and "
                  "events)", file=sys.stderr)
            return 2
        _enable_metrics(args.metrics_json)
        from .sim.mc import replay_schedule

        result = replay_schedule(args.replay_schedule)
        boxes = result.pop("blackboxes", None) or {}
        box_paths = []
        for name, text in sorted(boxes.items()):
            box_path = f"blackbox_replay_{name}.txt"
            try:
                with open(box_path, "w") as f:
                    f.write(text)
            except OSError:
                continue
            box_paths.append(box_path)
        if box_paths:
            print(f"# flight recorders: {', '.join(box_paths)}",
                  file=sys.stderr)
        print(json.dumps(result))
        if result["error"]:
            print(f"error: replay diverged: {result['error']}",
                  file=sys.stderr)
            return 1
        if not result["reproduced"]:
            print("error: recorded violation did not reproduce",
                  file=sys.stderr)
            return 1
        if not result["identical"]:
            print("error: violation reproduced but the canonical state "
                  "key differs", file=sys.stderr)
            return 1
        return 0

    if args.tpu and (
        args.overload or args.no_priority
        or args.byzantine or args.no_verify or args.merkle
        or args.catchup or args.force_full or args.lying_responder
        or args.reconfig
    ):
        # Same loud-reject discipline as the non-TPU knob checks below:
        # the TPU vopr runs its own random schedule, so silently dropping
        # --overload would report a scenario that never ran.
        print("error: --overload/--no-priority/--byzantine/--no-verify/"
              "--merkle/--catchup/--reconfig do not apply with --tpu",
              file=sys.stderr)
        return 2
    if args.tpu:
        from .sim import vopr_tpu

        # Round-5 drift fix: the assert (and --bug choices) had fallen
        # behind BUGS when amputate_vouch/join_keep_stale landed — any
        # `vopr --tpu` invocation tripped it.
        assert set(vopr_tpu.BUGS) == {
            "commit_quorum", "canonical_by_op", "no_truncate",
            "corrupt_serve", "wal_wrap", "split_brain",
            "amputate_vouch", "join_keep_stale", "scrub_off",
        }, "cli --bug choices drifted from sim.vopr_tpu.BUGS"
        if args.count != 1 or args.ticks is not None:
            print("error: --count/--ticks apply only without --tpu",
                  file=sys.stderr)
            return 2
        seed = args.seed if args.seed is not None else secrets.randbits(31)
        violations = vopr_tpu.run_sharded(
            seed=seed,
            n_clusters=args.clusters,
            n_steps=args.steps,
            bug=args.bug,
            # scrub_off only bites when silent SDC is actually injected.
            **({"p_sdc": 0.3} if args.bug == "scrub_off" else {}),
        )
        n = int(violations.sum())
        print(
            f"vopr-tpu: seed={seed} {len(violations)} clusters x "
            f"{args.steps} steps, {n} safety violations"
            + (f" (bug={args.bug} injected)" if args.bug else "")
        )
        if args.bug:
            return 0 if n > 0 else 1  # the oracle must catch a known bug
        return EXIT_CORRECTNESS if n > 0 else 0

    from .sim.vopr import (
        run_byzantine_seed, run_catchup_seed, run_overload_seed,
        run_reconfig_seed, run_seed,
    )

    if args.bug is not None or args.clusters != 4096 or args.steps != 400:
        print("error: --clusters/--steps/--bug apply only with --tpu",
              file=sys.stderr)
        return 2
    if args.no_priority and not args.overload:
        print("error: --no-priority applies only with --overload",
              file=sys.stderr)
        return 2
    if args.no_verify and not (
        args.byzantine or args.catchup or args.reconfig
    ):
        print("error: --no-verify applies only with --byzantine, "
              "--catchup or --reconfig", file=sys.stderr)
        return 2
    if (args.primary_seat or args.auth) and not args.byzantine:
        print("error: --primary-seat/--auth apply only with --byzantine",
              file=sys.stderr)
        return 2
    if (args.force_full or args.lying_responder) and not args.catchup:
        print("error: --force-full/--lying-responder apply only with "
              "--catchup", file=sys.stderr)
        return 2
    if args.catchup and (
        args.overload or args.byzantine or args.device_faults
        or args.scrub_interval is not None or args.merkle
        or args.vopr_viz or args.ticks is not None
    ):
        # The catch-up scenario owns its schedule (merkle is ALWAYS armed
        # there — it is the incremental transport's precondition); loudly
        # reject knobs it does not take.
        print("error: --overload/--byzantine/--device-faults/"
              "--scrub-interval/--merkle/--vopr-viz/--ticks do not apply "
              "with --catchup", file=sys.stderr)
        return 2
    if args.reconfig and (
        args.overload or args.byzantine or args.catchup
        or args.device_faults or args.scrub_interval is not None
        or args.merkle or args.vopr_viz or args.ticks is not None
    ):
        # The reconfiguration scenario owns its schedule (fixed reshard/
        # promotion/kill ticks); loudly reject knobs it does not take.
        print("error: --overload/--byzantine/--catchup/--device-faults/"
              "--scrub-interval/--merkle/--vopr-viz/--ticks do not apply "
              "with --reconfig", file=sys.stderr)
        return 2
    if args.merkle and not args.scrub_interval:
        print("error: --merkle needs --scrub-interval >= 1 (the commitment "
              "tree arms at the scrub cadence; docs/commitments.md)",
              file=sys.stderr)
        return 2
    if args.byzantine and (
        args.overload or args.device_faults
        or args.scrub_interval is not None or args.vopr_viz or args.merkle
    ):
        # Same loud-rejection discipline as --overload: the byzantine
        # scenario owns its schedule; silently dropping a knob would
        # report a run that never happened.
        print("error: --overload/--device-faults/--scrub-interval/"
              "--merkle/--vopr-viz do not apply with --byzantine",
              file=sys.stderr)
        return 2
    if args.overload and (
        args.ticks is not None or args.scrub_interval is not None
        or args.vopr_viz or args.merkle
    ):
        # Loudly reject knobs the overload kind does not take (its tick
        # budget and scrub cadence are fixed by the scenario) rather than
        # silently running with different parameters than the user asked.
        print("error: --ticks/--scrub-interval/--merkle/--vopr-viz do "
              "not apply with --overload", file=sys.stderr)
        return 2
    _enable_metrics(args.metrics_json)
    first = args.seed if args.seed is not None else secrets.randbits(31)
    worst = 0
    for seed in range(first, first + args.count):
        if args.reconfig:
            result = run_reconfig_seed(seed, verify=not args.no_verify)
            print(
                f"seed={result.seed} exit={result.exit_code} "
                f"verify={result.verify} promoted={result.promoted} "
                f"crash_source={result.crash_source} "
                f"killed_primary={result.killed_primary} "
                f"shards={result.shards_final} "
                f"stats={result.reshard_stats}: {result.reason}"
            )
            worst = max(worst, result.exit_code)
            continue
        if args.catchup:
            result = run_catchup_seed(
                seed,
                force_full=args.force_full,
                lying_responder=args.lying_responder,
                verify=not args.no_verify,
            )
            print(
                f"seed={result.seed} exit={result.exit_code} "
                f"rejoiner={result.rejoiner} mode={result.sync_mode} "
                f"ops_advanced={result.ops_advanced} "
                f"sync={result.sync_stats}: {result.reason}"
            )
            worst = max(worst, result.exit_code)
            continue
        if args.byzantine:
            result = run_byzantine_seed(
                seed,
                verify=not args.no_verify,
                ticks=args.ticks if args.ticks is not None else 2_600,
                primary_seat=args.primary_seat,
                auth=args.auth,
            )
            print(
                f"seed={result.seed} exit={result.exit_code} "
                f"byz_replica={result.byz_replica} "
                f"verify={result.verify} "
                f"primary_seat={result.primary_seat} auth={result.auth} "
                f"attacks={result.attacks} "
                f"rejected={result.rejected} "
                f"auth_counters={result.auth_counters} "
                f"detected={result.equivocations_detected}: {result.reason}"
            )
            worst = max(worst, result.exit_code)
            continue
        if args.overload:
            result = run_overload_seed(
                seed,
                priority=not args.no_priority,
                device_faults=args.device_faults,
            )
            print(
                f"seed={result.seed} exit={result.exit_code} "
                f"flood={result.flood_clients} "
                f"vc_tick={result.view_change_tick} "
                f"stats={result.stats}: {result.reason}"
            )
            worst = max(worst, result.exit_code)
            continue
        result = run_seed(
            seed,
            ticks=args.ticks if args.ticks is not None else 6_000,
            viz=True if args.vopr_viz else None,
            scrub_interval=args.scrub_interval or 0,
            merkle=args.merkle,
            device_faults=args.device_faults,
        )
        print(
            f"seed={result.seed} exit={result.exit_code} "
            f"commits={result.commits} faults={result.faults} "
            f"ticks={result.ticks}: {result.reason}"
        )
        if result.exit_code != 0 and result.viz is not None:
            # Debuggable finds, not opaque seeds: the full grid lands in a
            # file, the tail (where the failure is) on stderr.
            viz_path = f"vopr_viz_{result.seed}.txt"
            try:
                with open(viz_path, "w") as f:
                    f.write(result.viz + "\n")
                print(f"# cluster visualization: {viz_path}",
                      file=sys.stderr)
            except OSError as err:
                print(f"# could not write {viz_path}: {err}",
                      file=sys.stderr)
            tail = result.viz.splitlines()
            for line in tail[:2] + tail[max(2, len(tail) - 20):]:
                print(f"# {line}", file=sys.stderr)
        if result.exit_code != 0 and getattr(result, "blackboxes", None):
            # Per-replica flight-recorder dumps ride next to the viz grid
            # (docs/tracing.md): the protocol history leading into the
            # failure, one postmortem file per seat.
            box_paths = []
            for name, text in sorted(result.blackboxes.items()):
                box_path = f"blackbox_{result.seed}_{name}.txt"
                try:
                    with open(box_path, "w") as f:
                        f.write(text)
                except OSError as err:
                    print(f"# could not write {box_path}: {err}",
                          file=sys.stderr)
                    continue
                box_paths.append(box_path)
            if box_paths:
                print(f"# flight recorders: {', '.join(box_paths)}",
                      file=sys.stderr)
        worst = max(worst, result.exit_code)
    return worst


def _cmd_format(args) -> int:
    from .vsr.replica import Replica

    try:
        Replica.format(
            args.path, cluster=args.cluster, replica=args.replica,
            replica_count=args.replica_count,
            standby_count=args.standby_count,
        )
    except ValueError as err:
        print(f"error: {err}", file=sys.stderr)
        return 1
    role = (
        "standby" if args.replica >= args.replica_count else "replica"
    )
    print(f"formatted {args.path} (cluster {args.cluster:#x}, "
          f"{role} {args.replica}/{args.replica_count}"
          + (f"+{args.standby_count}" if args.standby_count else "") + ")")
    return 0


def _cmd_promote(args) -> int:
    from .vsr.replica import Replica

    try:
        Replica.promote(args.path, args.replica)
    except ValueError as err:
        print(f"error: {err}", file=sys.stderr)
        return 1
    print(f"promoted {args.path} to voting replica {args.replica}")
    return 0


def _make_statsd(value):
    """Build a StatsD sink from an already-validated (host, port) pair
    (the --statsd argparse type, _statsd_addr)."""
    if not value:
        return None
    from .utils.statsd import StatsD

    host, port = value
    return StatsD(host, port)


def _enable_metrics(path):
    """Opt the process into the metrics registry for a --metrics-json run:
    series record from here on, jit compiles are accounted, and the caller
    (or atexit, for the serve-forever paths) dumps the snapshot to
    ``path``."""
    if not path:
        return None
    from . import jaxenv
    from .obs.metrics import registry

    registry.enable()
    jaxenv.instrument_compiles()
    import atexit

    @atexit.register
    def _dump() -> None:
        try:
            registry.dump(path)
        except OSError:
            return
        print(f"metrics: wrote snapshot to {path}", file=sys.stderr)

    _install_sigterm_atexit()
    return registry


def _report_device_at_exit(machine, warmup_s: float) -> None:
    """With --metrics-json, fold what only the serving process can see of
    its device into the exit snapshot: warm-up seconds, the bytes of the
    ledger each device holds (from the arrays' own shards, so it reads the
    same on every backend), and the allocator's in-use/peak bytes where the
    backend reports them (XLA-CPU does not).  Registered AFTER
    _enable_metrics' dump, so atexit's LIFO order runs it first."""
    from .obs.metrics import registry

    if not registry.enabled:
        return
    registry.gauge("start.warmup_s").set(round(warmup_s, 3))
    import atexit

    import jax

    @atexit.register
    def _report() -> None:
        held = {d.id: 0 for d in jax.devices()}
        for leaf in jax.tree_util.tree_leaves(machine._ledger):
            for shard in getattr(leaf, "addressable_shards", ()):
                held[shard.device.id] += shard.data.nbytes
        for dev in jax.devices():
            registry.gauge(f"device.{dev.id}.ledger_bytes").set(held[dev.id])
            stats = dev.memory_stats() or {}
            for key in ("bytes_in_use", "peak_bytes_in_use"):
                if key in stats:
                    registry.gauge(f"device.{dev.id}.{key}").set(stats[key])


def _install_sigterm_atexit() -> None:
    """Servers are stopped with SIGTERM, whose default handler skips
    atexit — but every exit-time observability dump (metrics snapshot,
    TB_TRACE trace, TB_BLACKBOX flight recorder) rides atexit.  Raising
    SystemExit unwinds serve_forever and runs them; only installed when
    nothing else claimed the signal.  Idempotent."""
    import signal

    def _on_sigterm(signum, frame):
        raise SystemExit(143)

    try:
        if signal.getsignal(signal.SIGTERM) == signal.SIG_DFL:
            signal.signal(signal.SIGTERM, _on_sigterm)
    except (ValueError, OSError):
        pass  # non-main thread or unsupported platform: atexit still covers
              # normal exits


def _arm_blackbox(replica) -> None:
    """Attach the flight recorder (obs/txtrace.Blackbox) when TB_BLACKBOX
    is set — ``1`` for the default ring, a larger integer for a deeper
    one — and dump it at process exit, covering crash-path exits
    (unhandled server faults, KeyboardInterrupt, the SIGTERM handler's
    atexit re-raise) as well as normal shutdown.  Device-recovery dumps
    (replica.dump_blackbox) fire independently of this hook."""
    spec = os.environ.get("TB_BLACKBOX", "")
    if not spec or spec == "0":
        return
    from .obs.txtrace import Blackbox

    cap = int(spec) if spec.isdigit() and int(spec) > 1 else 512
    replica.blackbox = Blackbox(f"r{replica.replica}", cap=cap)
    import atexit

    atexit.register(lambda: replica.dump_blackbox("exit"))


# What a table can take: 2^32 slots of the narrowest table (posted, 21 B a
# slot) are more than the memory of any device this serves from.
_TABLE_LOG2_MAX = 32
# The cold tier's filter: what `ops/cold.make_bloom` takes (128 B to 2 GiB),
# and the least a `start` without `--cold-bloom-log2` allocates (128 KiB).
_BLOOM_LOG2_MIN, _BLOOM_LOG2_MAX, _BLOOM_LOG2_DEFAULT_MIN = 10, 34, 20


def _ledger_config(args):
    """`start`'s three table options onto the default LedgerConfig, each on
    its own, and under `--hot-transfers-log2-max` the cold tier's filter
    (`--cold-bloom-log2`; default: the hot window's log2 + 6, 12 bits an id
    for a cold store of eight windows).  Raises ValueError for a size the
    tables cannot take: nothing is clamped, because a server with other
    tables than its operator asked for is another deployment."""
    import dataclasses

    from .config import LedgerConfig

    sizes = {
        "accounts": args.cache_accounts_log2,
        "transfers": args.cache_transfers_log2,
        "posted": args.cache_posted_log2,
    }
    if sizes["transfers"] is None and sizes["accounts"] is not None:
        sizes["transfers"] = sizes["accounts"] + 2
    shards = args.shards
    if shards is None:
        env = os.environ.get("TB_SHARDS", "")
        shards = int(env) if env.isdigit() else 0
    log2_min = max(1, shards).bit_length() - 1  # a slot for every shard
    for table, log2 in sizes.items():
        if log2 is not None and not log2_min <= log2 <= _TABLE_LOG2_MAX:
            raise ValueError(
                f"--cache-{table}-log2 {log2}: the {table} table takes "
                f"2^{log2_min} to 2^{_TABLE_LOG2_MAX} slots"
                + (f" under --shards {shards}" if shards >= 2 else "")
            )
    fields = {
        f"{table}_capacity_log2": log2
        for table, log2 in sizes.items() if log2 is not None
    }
    hot_log2 = getattr(args, "hot_transfers_log2_max", None)
    bloom_log2 = getattr(args, "cold_bloom_log2", None)
    if bloom_log2 is not None and hot_log2 is None:
        raise ValueError(
            "--cold-bloom-log2 sizes the cold tier's filter: it needs "
            "--hot-transfers-log2-max")
    if hot_log2 is not None:
        if bloom_log2 is None:
            bloom_log2 = min(_BLOOM_LOG2_MAX,
                             max(_BLOOM_LOG2_DEFAULT_MIN, hot_log2 + 6))
        if not _BLOOM_LOG2_MIN <= bloom_log2 <= _BLOOM_LOG2_MAX:
            raise ValueError(
                f"--cold-bloom-log2 {bloom_log2}: the filter takes "
                f"2^{_BLOOM_LOG2_MIN} to 2^{_BLOOM_LOG2_MAX} bits")
        fields["bloom_bits_log2"] = bloom_log2
    return dataclasses.replace(LedgerConfig(), **fields)


def _cmd_start(args) -> int:
    from .net.bus import run_server
    from .vsr.replica import Replica

    # Enable BEFORE the replica/machine construct so every series —
    # including warmup's jit compiles — is captured; the atexit dump covers
    # both the serve-forever exit and KeyboardInterrupt.
    _enable_metrics(args.metrics_json)
    # TB_TRACE / TB_BLACKBOX dumps ride atexit too — a SIGTERM-stopped
    # server must still land them even without --metrics-json.
    _install_sigterm_atexit()

    def export_options() -> None:
        """The options whose env twins the constructors read, written once
        every option has been accepted: a refused `start` leaves the
        environment as it found it."""
        if args.overload_control:
            # One knob for every layer (consensus shed points, both
            # buses): what VsrReplica/ReplicaServer constructors read.
            os.environ["TB_OVERLOAD"] = "1"
        if args.shards is not None:
            # What the TpuStateMachine constructor reads (the machine is
            # built inside Replica/VsrReplica).
            os.environ["TB_SHARDS"] = str(max(0, args.shards))

    if args.shards is not None:
        if args.shards < 0 or (
            args.shards >= 2 and args.shards & (args.shards - 1) != 0
        ):
            # Validate at the CLI boundary: the machine's internal check is
            # an assert, which must never be an operator's first error.
            print(f"error: --shards must be 0 or a power of two, got "
                  f"{args.shards}", file=sys.stderr)
            return 1
        if args.shards >= 2 and args.hot_transfers_log2_max is not None:
            print("error: --shards and --hot-transfers-log2-max are "
                  "exclusive (cold tiering is a single-device concern; "
                  "docs/sharding.md)", file=sys.stderr)
            return 1
        if args.shards >= 2 and args.engine:
            print("error: --shards runs on the device path; --engine "
                  "commits through the native host engine — pick one",
                  file=sys.stderr)
            return 1
        if args.shards > args.backend[2]:
            print(f"error: --shards {args.shards} needs {args.shards} "
                  f"devices, {args.backend[2]} visible "
                  f"({args.backend[0]})", file=sys.stderr)
            return 1

    if args.merkle:
        env_iv = os.environ.get("TB_SCRUB_INTERVAL", "")
        interval = args.scrub_interval if args.scrub_interval is not None \
            else (int(env_iv) if env_iv.isdigit() else 0)
        if interval <= 0:
            # Loud-reject discipline (same knob contract as vopr): with no
            # scrub cadence the commitment tree never arms, and the server
            # would silently serve with no checks and no proofs.
            print("error: --merkle needs --scrub-interval >= 1 (or "
                  "TB_SCRUB_INTERVAL) — the commitment tree arms at the "
                  "scrub cadence (docs/commitments.md)", file=sys.stderr)
            return 1
        if args.engine:
            print("error: --merkle runs on the device path; --engine "
                  "commits through the native host engine — pick one",
                  file=sys.stderr)
            return 1

    import dataclasses as _dc

    from .config import PROCESS_DEFAULT

    process_config = _dc.replace(
        PROCESS_DEFAULT,
        direct_io=bool(args.direct_io),
        direct_io_required=bool(args.direct_io_required),
        **({"tick_ms": args.tick_ms} if args.tick_ms is not None else {}),
    )

    try:
        ledger_config = _ledger_config(args)
    except ValueError as err:
        print(f"error: {err}", file=sys.stderr)
        return 1
    addresses = _parse_addresses(args.addresses)
    if len(addresses) > 1:
        # Multi-replica cluster: full VSR consensus over the TCP bus.  The
        # replica's own address is addresses[replica_index] (cli.zig
        # --addresses semantics).
        from .net.cluster_bus import run_cluster_server
        from .vsr.consensus import VsrReplica

        if args.no_engine:
            print("error: --no-engine applies to single-replica serving "
                  "only (cluster replicas already default to the device "
                  "path); did you mean to omit it?", file=sys.stderr)
            return 1
        if args.engine:
            from .host_engine import engine_available as _engine_ok

            if not _engine_ok():
                # Dropping the flag silently would serve a different
                # executor than the operator asked for.
                print("error: --engine requested but the native host "
                      "engine failed to build", file=sys.stderr)
                return 1

        export_options()
        replica = VsrReplica(
            args.path, ledger_config=ledger_config, aof_path=args.aof,
            process_config=process_config, host_engine=bool(args.engine),
            scrub_interval=args.scrub_interval,
            merkle=True if args.merkle else None,
        )
        if args.pipeline_depth is not None:
            replica.pipeline_depth = args.pipeline_depth
        auth_secret = os.environ.get("TB_AUTH_SECRET", "")
        if auth_secret:
            # Wire authentication (vsr/auth.py): every replica of the
            # cluster must export the SAME secret (hex, >= 16 bytes).
            # TB_AUTH_STRICT=0 downgrades to accept-and-count for rolling
            # deployment alongside auth-off peers (docs/fault_domains.md).
            from .vsr.auth import Keychain

            try:
                secret = bytes.fromhex(auth_secret)
            except ValueError:
                secret = b""
            if len(secret) < 16:
                print("error: TB_AUTH_SECRET must be >= 16 bytes of hex",
                      file=sys.stderr)
                return 1
        replica.open()
        if auth_secret:
            replica.auth = Keychain(replica.cluster, secret=secret)
            replica.auth_strict = (
                os.environ.get("TB_AUTH_STRICT", "1") != "0"
            )
        _arm_blackbox(replica)
        _announce_backend(
            args.backend,
            executor="host_engine" if args.engine else "device",
        )
        replica.machine.warmup()  # compile before announcing readiness
        host = addresses[replica.replica][0]

        def ready(actual_port):
            print(f"listening {host}:{actual_port}", flush=True)

        run_cluster_server(
            replica, addresses, ready_callback=ready,
            statsd=_make_statsd(args.statsd),
        )
        return 0

    hot_max = (
        1 << args.hot_transfers_log2_max
        if args.hot_transfers_log2_max is not None else None
    )
    # Solo-server data plane: commits run in the native host engine when it
    # builds (host_engine.py) — the latency-bound OLTP path doesn't round-
    # trip the (possibly remote) accelerator per batch.  Tiering keeps the
    # device path (the hot/cold window lives in device memory); --no-engine
    # forces it for debugging.
    from .host_engine import engine_available

    if args.engine:
        print("error: --engine applies to multi-replica serving only (the "
              "solo server already uses the host engine when it builds; "
              "--no-engine forces the device path)", file=sys.stderr)
        return 1
    use_engine = (
        engine_available() and hot_max is None and not args.no_engine
        # Sharding runs on the device path only: the mesh ledger IS the
        # serving authority, never the numpy engine mirror.
        and not (args.shards or 0) >= 2
        # Merkle commitments live on the device path too: the forest
        # commits to the device pads (scrub_arm is a no-op in host-engine
        # mode, where the numpy ledger is already the authority).  The
        # env twin must behave exactly like the flag.
        and not args.merkle
        and os.environ.get("TB_MERKLE", "") != "1"
    )
    export_options()
    replica = Replica(args.path, ledger_config=ledger_config,
                      aof_path=args.aof, hot_transfers_capacity_max=hot_max,
                      process_config=process_config, host_engine=use_engine,
                      scrub_interval=args.scrub_interval,
                      merkle=True if args.merkle else None)
    if args.pipeline_depth is not None:
        replica.pipeline_depth = args.pipeline_depth
    replica.open()
    if replica.replica_count != 1:
        # A multi-replica data file must never be served solo: commits
        # without the quorum would fork the cluster's log (split brain).
        print(
            f"error: data file is replica {replica.replica} of a "
            f"{replica.replica_count}-replica cluster; pass all "
            f"{replica.replica_count} --addresses",
            file=sys.stderr,
        )
        return 1
    (host, port), = addresses
    _arm_blackbox(replica)
    _announce_backend(
        args.backend, executor="host_engine" if use_engine else "device",
    )
    # Compile the commit kernels BEFORE announcing readiness: the first
    # create_transfers otherwise eats the full jit latency inside a client's
    # request timeout window.
    t0 = time.monotonic()
    replica.machine.warmup()
    _report_device_at_exit(replica.machine, time.monotonic() - t0)

    def ready(actual_port):
        # Port-0 trick for tooling (reference main.zig:239-264): print the
        # bound port on stdout so a parent process can parse it.
        print(f"listening {host}:{actual_port}", flush=True)

    run_server(replica, host, port, ready_callback=ready,
               statsd=_make_statsd(args.statsd))
    return 0


def _cmd_version(args) -> int:
    from .config import PRESETS

    print("tigerbeetle-tpu 0.1.0")
    if args.verbose:
        # Full resolved runtime config (main.zig:272-310 version --verbose
        # dumps every config constant; config.zig:206-303 preset split):
        # the preset matrix, the jax backend actually serving this process,
        # the compile cache, and the observability env toggles.
        import jax

        from . import jaxenv

        for preset in PRESETS.values():
            for level in ("cluster", "process", "ledger"):
                for key, value in vars(getattr(preset, level)).items():
                    print(f"  {preset.name}.{level}.{key}={value}")
        devices = jax.devices()
        print(f"  jax.version={jax.__version__}")
        print(f"  jax.backend={devices[0].platform}")
        print(f"  jax.device_count={len(devices)}")
        print(f"  jax.devices={[str(d) for d in devices]}")
        if jaxenv.DEGRADED_DEVICE_COUNT is not None:
            print(f"  jax.degraded_device_count="
                  f"{jaxenv.DEGRADED_DEVICE_COUNT}")
        print(f"  compile_cache.dir={jaxenv.COMPILE_CACHE_DIR}")
        print(f"  compile_cache.env="
              f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', '')}")
        for env in ("TB_TRACE", "TB_TRACE_PATH", "TB_METRICS_PATH",
                    "TB_VOPR_VIZ", "TB_PIPELINE", "TB_SCRUB_INTERVAL",
                    "TB_OVERLOAD", "JAX_PLATFORMS"):
            print(f"  env.{env}={os.environ.get(env, '')}")
    return 0


def _cmd_repl(args) -> int:
    from . import repl as repl_mod
    from .client import Client

    client = Client(_parse_addresses(args.addresses), cluster=args.cluster)
    try:
        repl_mod.run(client, args.command)
    finally:
        client.close()
    return 0


def _cmd_benchmark(args) -> int:
    """Client-driven load (benchmark_load.zig:13-17: create accounts, stream
    transfer batches, print accepted tx/s + batch latency percentiles)."""
    from . import types
    from .client import Client

    stack = []
    if args.addresses is None:
        addresses, cleanup = _spawn_temp_replica(args.cluster)
        stack.append(cleanup)
    else:
        addresses = _parse_addresses(args.addresses)

    try:
        client = Client(addresses, cluster=args.cluster)
        rng = np.random.default_rng(42)

        # Random id base: repeated runs against a used cluster don't collide.
        import secrets

        id_base = secrets.randbits(30) << 32

        n = args.account_count
        accounts = np.zeros(n, dtype=types.ACCOUNT_DTYPE)
        accounts["id_lo"] = id_base + np.arange(1, n + 1, dtype=np.uint64)
        accounts["ledger"] = 2
        accounts["code"] = 1
        for start in range(0, n, args.transfer_batch_size):
            results = client.create_accounts(
                accounts[start : start + args.transfer_batch_size]
            )
            assert not results, f"account failures: {results[:3]}"

        total = args.transfer_count
        batch_size = args.transfer_batch_size
        latencies = []
        accepted = 0
        tid = secrets.randbits(30) << 33
        t0 = time.monotonic()
        sent = 0
        warmed = False
        while sent < total:
            count = min(batch_size, total - sent)
            batch = np.zeros(count, dtype=types.TRANSFER_DTYPE)
            batch["id_lo"] = np.arange(tid, tid + count, dtype=np.uint64)
            dr = rng.integers(1, n + 1, count, dtype=np.uint64)
            off = rng.integers(1, n, count, dtype=np.uint64)
            batch["debit_account_id_lo"] = id_base + dr
            batch["credit_account_id_lo"] = id_base + (dr - 1 + off) % n + 1
            batch["amount_lo"] = rng.integers(1, 1 << 16, count, dtype=np.uint64)
            batch["ledger"] = 2
            batch["code"] = 1
            bt0 = time.monotonic()
            results = client.create_transfers(batch)
            if warmed:
                latencies.append(time.monotonic() - bt0)
                accepted += count - len(results)
            else:
                # First batch pays one-time jit latency even after the
                # server-side warmup (per-process caches): restart the
                # timer and exclude it, so throughput and percentiles
                # measure steady state (benchmark_load.zig likewise).
                warmed = True
                warmup_latency = time.monotonic() - bt0
                warmup_accepted = count - len(results)
                t0 = time.monotonic()
            sent += count
            tid += count
        elapsed = max(time.monotonic() - t0, 1e-9)
        if not latencies:
            # Single-batch run: the warmup sample is all there is.
            latencies = [warmup_latency]
            accepted = warmup_accepted
            elapsed = max(warmup_latency, 1e-9)

        lat_ms = sorted(1e3 * l for l in latencies)

        def pct(p):
            return lat_ms[min(len(lat_ms) - 1, int(p / 100 * len(lat_ms)))]

        print(f"load accepted = {accepted / elapsed:,.0f} tx/s")
        print(f"batch latency p50 = {pct(50):.2f} ms, p95 = {pct(95):.2f} ms, "
              f"p99 = {pct(99):.2f} ms, max = {lat_ms[-1]:.2f} ms")
        print(json.dumps({
            "metric": "benchmark_load_accepted",
            "value": round(accepted / elapsed, 1),
            "unit": "tx/s",
            "vs_baseline": round(accepted / elapsed / 1_000_000, 3),
        }))
        client.close()
        return 0
    finally:
        for cleanup in stack:
            cleanup()


def _spawn_temp_replica(cluster: int):
    """Format + serve a temp single replica in-process (benchmark_driver.zig
    spawns a child; a daemon thread keeps this self-contained)."""
    from .config import LedgerConfig
    from .net.bus import run_server
    from .vsr.replica import Replica

    from .config import ProcessConfig
    from .host_engine import engine_available

    tmp = tempfile.mkdtemp(prefix="tb_bench_")
    path = os.path.join(tmp, "bench.tb")
    Replica.format(path, cluster=cluster)
    replica = Replica(
        path,
        ledger_config=LedgerConfig(
            accounts_capacity_log2=21, transfers_capacity_log2=23,
            posted_capacity_log2=16,
        ),
        host_engine=engine_available(),
        process_config=ProcessConfig(direct_io=True),
    )
    replica.open()

    port_box = {}
    ready = threading.Event()

    def serve():
        run_server(replica, "127.0.0.1", 0,
                   ready_callback=lambda p: (port_box.update(port=p), ready.set()))

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    assert ready.wait(30), "temp replica failed to start"

    def cleanup():
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)

    return [("127.0.0.1", port_box["port"])], cleanup


if __name__ == "__main__":
    sys.exit(main())
