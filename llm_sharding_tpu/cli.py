"""Operator CLI: ``python -m llm_sharding_tpu <command>``.

The reference is driven from a shell — per-node daemons (``start_node.py:
6-20``), a config pusher (``send_config.py:5-48``), profiler entries
(``profiling.py:1-19``), a monolithic baseline (``inference.py:36-49``) and a
pod launcher (``run_this.sh:8-17``). One host owning the whole mesh collapses
those five entry points into subcommands:

- ``convert``  — HF checkpoint → shard store (≙ running ``model_sharder.py``)
- ``generate`` — one prompt through the sharded pipeline (≙ ``inference.py``,
  but pipelined; ``--stream`` streams tokens from the sharded program)
- ``serve``    — persistent interactive daemon over stdin (≙ ``start_node.py``
  + ``run_worker_loop``), continuous batching underneath; ``--metrics-port``
  exposes /metrics (Prometheus) + /statz (JSON) + a live /healthz,
  ``--trace-path`` streams JSONL latency spans, ``:stats`` prints the
  telemetry snapshot in-band; ``--max-queue``/``--default-deadline`` shed
  load, ``--snapshot-every``/``--snapshot-dir`` auto-checkpoint for crash
  recovery (``--restore DIR`` resumes)
- ``profile``  — capability sweeps, hop latency, artifacts + an optional
  capability-weighted placement suggestion (≙ ``profiling.py``; closes the
  profiler→scheduler loop of the reference's README)

Placements: ``--stages N`` for a balanced split or ``--ranges 0:6,6:7,7:32``
for the reference-style ragged chains (``send_config.py:10-34``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import signal
import sys
import threading
import time

import numpy as np


def _stdin_lines(stop_evt):
    """Prompt lines from stdin, waking every 200 ms to honor a SIGTERM
    (``stop_evt``) even while blocked waiting for input. Falls back to
    plain iteration when stdin is not selectable (tests monkeypatch a
    ``StringIO``; pipes and TTYs take the select path).

    The select path reads the fd RAW (``os.read``) and splits lines
    itself: mixing ``select()`` with buffered ``sys.stdin.readline()``
    strands any second line of a burst in Python's read-ahead buffer,
    where select — which only sees the OS pipe — never reports it."""
    try:
        fd = sys.stdin.fileno()
        import select as _select

        _select.select([fd], [], [], 0)
    except Exception:  # noqa: BLE001 — no real fd / select unsupported
        yield from sys.stdin
        return
    buf = ""
    while not stop_evt.is_set():
        r, _, _ = _select.select([fd], [], [], 0.2)
        if not r:
            continue
        chunk = os.read(fd, 65536)
        if not chunk:  # EOF (^D / closed pipe)
            if buf:
                yield buf
            return
        buf += chunk.decode("utf-8", errors="replace")
        while "\n" in buf:
            line, buf = buf.split("\n", 1)
            yield line + "\n"


def _dtype(name: str):
    import jax.numpy as jnp

    table = {
        "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
        "f32": jnp.float32, "float32": jnp.float32,
        "f16": jnp.float16, "float16": jnp.float16,
    }
    if name not in table:
        hint = (
            f" ({name} is a convert-time option; {name} stores load with "
            "any compute dtype — pass e.g. --dtype bf16)"
            if name in ("int8", "int4") else ""
        )
        raise SystemExit(
            f"unknown dtype {name!r}; choose from {sorted(set(table))}{hint}"
        )
    return table[name]


def _parse_ranges(text: str):
    ranges = []
    for part in text.split(","):
        a, b = part.split(":")
        ranges.append((int(a), int(b)))
    return ranges


def _placement(args, num_layers: int):
    from .parallel.placement import PlacementSpec

    if getattr(args, "ranges", None):
        return PlacementSpec.from_ranges(_parse_ranges(args.ranges), num_layers)
    if getattr(args, "stages", None):
        return PlacementSpec.balanced(num_layers, args.stages)
    return None


def _engine(args):
    from .runtime.engine import PipelineEngine
    from .utils import shard_store

    cfg = shard_store.load_config(args.shards)
    placement = _placement(args, cfg.num_hidden_layers)
    return PipelineEngine.from_shards(
        args.shards,
        placement=placement,
        num_stages=None if placement else getattr(args, "stages", None),
        dtype=_dtype(args.dtype),
        tensor_parallel=getattr(args, "tensor_parallel", 1),
    )


def cmd_convert(args) -> int:
    import jax
    import jax.numpy as jnp

    # an offline file transform: the quantizer's jnp ops run on the host,
    # and the chip stays free for whichever process serves
    jax.config.update("jax_platforms", "cpu")

    from .utils.shard_store import convert_hf_checkpoint

    if args.dtype in ("int8", "int4"):
        # ≙ the reference's load_in_8bit/load_in_4bit conversions
        # (model_sharder.py:28-45): layer matmul weights stored quantized +
        # per-channel bf16 scales; int4 packs two values per byte on disk
        dtype, quantize = jnp.bfloat16, True
        bits = 8 if args.dtype == "int8" else 4
    else:
        dtype, quantize, bits = _dtype(args.dtype), False, 8
    if args.quantize_head and not quantize:
        raise SystemExit("--quantize-head requires --dtype int8 or int4")
    cfg = convert_hf_checkpoint(
        args.model_dir, args.out_dir, dtype, quantize=quantize,
        quantize_head=args.quantize_head, quant_bits=bits,
    )
    print(
        f"converted {cfg.model_type} ({cfg.num_hidden_layers} layers, "
        f"vocab {cfg.vocab_size}{f', {args.dtype}' if quantize else ''}"
        f"{' incl. head' if args.quantize_head else ''}) "
        f"-> {args.out_dir}"
    )
    return 0


def cmd_generate(args) -> int:
    eng = _engine(args)
    if args.stream:
        # streaming goes through the shared continuous-batching server;
        # temperature/seed/top-k/top-p are all per-request row state there
        for delta in eng.generate_text_stream(
            args.prompt, args.max_new,
            temperature=args.temperature, seed=args.seed,
            top_k=args.top_k, top_p=args.top_p,
        ):
            print(delta, end="", flush=True)
        print()
    else:
        print(
            eng.generate_text(
                args.prompt, args.max_new, temperature=args.temperature,
                top_k=args.top_k, top_p=args.top_p, seed=args.seed,
            )
        )
    return 0


#: the serve flags whose ``dest`` is not the name of the option they set
#: (``runtime/options.ServeOptions``)
_FLAG_OF = {
    "default_deadline_s": "default_deadline",
    "snapshot_every_s": "snapshot_every",
    "snapshot_path": "snapshot_dir",
    "gauge_sweep_every_s": "gauge_sweep_every",
}


def _serve_flags():
    """(option name, its flag's ``dest``) for every serve option."""
    from .runtime.options import ServeOptions

    return [(n, _FLAG_OF.get(n, n)) for n in ServeOptions.names()]


def _serve_options(args):
    """The ``ServeOptions`` record of the serve flags. An option that
    defaults to None has a flag on which 0 (or nothing) means unset; an
    option without a flag keeps its default."""
    from .runtime.options import ServeOptions

    defaults = ServeOptions()
    kw = {}
    for name, flag in _serve_flags():
        if hasattr(args, flag):
            got = getattr(args, flag)
            kw[name] = (got or None) if getattr(defaults, name) is None else got
    return ServeOptions(**kw)


def _flag_error(e: ValueError) -> str:
    """A refusal of ``ServeOptions.validate`` as the daemon prints it: the
    record's own words, then the flags of the options they name."""
    import re

    named = [
        "--" + flag.replace("_", "-") for name, flag in _serve_flags()
        if re.search(rf"\b{name}\b", str(e))
    ]
    return f"error: {e} (flags: {', '.join(named)})"


def _serve_control(eng, srv, line: str, args):
    """Daemon control lines (≙ the reference's hot config push checked every
    loop iteration, ``/root/reference/utils/node_worker.py:445-474`` — there
    the master re-sends a JSON config over ZMQ; here the operator types a
    control line into the running daemon):

    - ``:placement 0:6,6:32`` — drain in-flight requests, hot-apply the new
      layer→stage mapping, rebuild the continuous-batching server on it
    - ``:placement 4``        — balanced split over 4 stages
    - ``:counters``           — print the running counters
    - ``:stats``              — print the full telemetry snapshot (counters +
      every registry metric, histograms with p50/p90/p99) as one JSON line —
      the stdin twin of the ``--metrics-port`` HTTP ``/statz`` endpoint
    - ``:snapshot DIR``       — checkpoint the live daemon (device state +
      in-flight/queued requests) to DIR; ``serve --restore DIR`` resumes it
    - ``:profile N [DIR]``    — arm an N-step deep capture on the step
      profiler (sub-phase timeline, lock waits, trace_id exemplars; with
      DIR also a ``jax.profiler`` device trace) and print the JSON bundle —
      the stdin twin of HTTP ``/profilez?steps=N``. Prints a partial
      bundle (``complete: false``) if the loop idles before N steps.

    Returns the (possibly new) server.
    """
    from .obs.metrics import REGISTRY
    from .parallel.placement import PlacementSpec

    parts = line.split(None, 1)
    cmd = parts[0]
    if cmd == ":counters":
        print(json.dumps(srv.counters.snapshot()), file=sys.stderr)
        return srv
    if cmd == ":stats":
        stats = {
            "counters": srv.counters.snapshot(),
            "metrics": REGISTRY.json_snapshot(),
            # step-profiler aggregates: host occupancy, p50 step wall
            "stepline": srv.stepline_stats(),
        }
        pc = srv.prefix_cache_stats()
        if pc is not None:
            # hit rate + tier occupancy for the operator tuning the cache
            stats["prefix_cache"] = pc
        gx = getattr(srv, "_gindex", None)
        if gx is not None:
            # the cluster-global radix index's routing view (dp >= 2)
            stats["global_index"] = gx.stats()
        print(json.dumps(stats, sort_keys=True), file=sys.stderr)
        return srv
    if cmd == ":profile":
        sub = parts[1].split() if len(parts) > 1 else []
        if not sub:
            print("usage: :profile N [TRACE_DIR]", file=sys.stderr)
            return srv
        try:
            bundle = srv.stepline_capture(
                int(sub[0]), trace_dir=sub[1] if len(sub) > 1 else None
            )
        except ValueError as e:
            print(f"profile failed: {e}", file=sys.stderr)
            return srv
        print(json.dumps(bundle, sort_keys=True), file=sys.stderr)
        return srv
    if cmd == ":snapshot":
        if len(parts) < 2:
            print("usage: :snapshot DIR", file=sys.stderr)
            return srv
        from .runtime.server import save_snapshot

        try:
            save_snapshot(srv.snapshot(), parts[1])
            print(f"snapshot written to {parts[1]}", file=sys.stderr)
        except (ValueError, RuntimeError, OSError) as e:
            print(f"snapshot failed: {e}", file=sys.stderr)
        return srv
    if cmd == ":placement":
        if len(parts) < 2:
            print("usage: :placement 0:6,6:32  |  :placement N", file=sys.stderr)
            return srv
        num_layers = eng.cfg.num_hidden_layers
        old_spec = eng.placement
        # in-flight requests finish on the old arrays, then swap; any failure
        # (bad ranges, more stages than devices) keeps the daemon serving on
        # the old placement — apply_placement only mutates on success
        try:
            if ":" in parts[1]:
                spec = PlacementSpec.from_ranges(
                    _parse_ranges(parts[1]), num_layers
                )
            else:
                spec = PlacementSpec.balanced(num_layers, int(parts[1]))
            srv.run_until_idle()
            counters = srv.counters
            eng.apply_placement(spec)
        except (ValueError, KeyError) as e:
            print(f"bad placement: {e}", file=sys.stderr)
            return srv
        def build():
            # the LIVE server's record, not args: a --restore'd daemon's
            # options came from the snapshot and may not be on the command
            # line at all — re-sharding must not silently reset capacity/
            # speculation/paged mode to the argparse defaults. (trace_path
            # stays args-sourced: the revived daemon's record has none.)
            return eng.serve(**vars(dataclasses.replace(
                srv.options, trace_path=getattr(args, "trace_path", None)
            )))

        try:
            new_srv = build()
            applied = spec
        except Exception as e:  # noqa: BLE001 — keep the daemon alive
            # The new placement's server failed to build (e.g. state
            # allocation OOM at the denser packing). The old server object
            # is unusable too — it reads the engine's (now swapped) arrays
            # live — so ROLL BACK the placement and rebuild on it.
            try:
                eng.apply_placement(old_spec)
                new_srv = build()
            except Exception as e2:  # noqa: BLE001
                # rollback failed too: no valid server exists on either
                # placement — print the session totals and stop cleanly
                # instead of crashing on the next prompt
                print(json.dumps(counters.snapshot()), file=sys.stderr)
                print(
                    f"placement rebuild failed ({e}) and rollback to "
                    f"{list(old_spec.stages)} also failed ({e2}); daemon "
                    "state is unrecoverable, exiting",
                    file=sys.stderr,
                )
                raise SystemExit(1)
            applied = old_spec
            print(
                f"placement rebuild failed ({e}); rolled back to "
                f"{list(old_spec.stages)}",
                file=sys.stderr,
            )
        srv.close()  # the discarded server's trace writer fd, not GC's job
        new_srv.counters = counters  # session totals survive the swap
        print(
            f"placement applied: {list(applied.stages)} over {eng.mesh.shape}",
            file=sys.stderr,
        )
        return new_srv
    print(f"unknown control line {cmd!r} (try :placement, :counters, "
          ":stats, :snapshot, :profile)",
          file=sys.stderr)
    return srv


def _dp_serve_control(srv, line: str):
    """dp daemon control lines (the elasticity surface of the replica
    supervision layer, ``runtime/replicated.py``):

    - ``:drain N``   — migrate every live request off replica N (device-
      group index, see ``:stats``) to the others and close it; refused
      below ``--min-replicas``. Scale-down drops zero streams.
    - ``:spawn``     — bring a fresh replica up on the lowest freed device
      group (weights re-staged from the shared host arrays).
    - ``:counters`` / ``:stats`` — as on the single-engine daemon, plus
      per-replica health/load/KV entries (with each replica's
      ``host_occupancy`` and ``step_wall_p50_ms``).
    - ``:profile N [DIR]`` — deep-capture fan-out: arm N steps on EVERY
      replica's step profiler, print ``{"r<d>": bundle}`` as JSON.

    Returns the server (the dp router object is never swapped)."""
    from .obs.metrics import REGISTRY

    parts = line.split(None, 1)
    cmd = parts[0]
    if cmd == ":counters":
        print(json.dumps(srv.counters.snapshot()), file=sys.stderr)
    elif cmd == ":stats":
        # the router's full view (aggregate counters, per-replica entries,
        # offline_groups — the ':spawn' decision input) + the registry
        print(
            json.dumps(
                {**srv.stats(), "metrics": REGISTRY.json_snapshot()},
                sort_keys=True,
            ),
            file=sys.stderr,
        )
    elif cmd == ":drain":
        if len(parts) < 2:
            print("usage: :drain N  (replica device-group index)",
                  file=sys.stderr)
            return srv
        try:
            moved = srv.drain(int(parts[1]))
            print(
                f"replica {int(parts[1])} drained: {moved} request(s) "
                f"migrated; {len(srv.servers)} replica(s) live",
                file=sys.stderr,
            )
        except (ValueError, RuntimeError) as e:
            print(f"drain failed: {e}", file=sys.stderr)
    elif cmd == ":spawn":
        try:
            s = srv.spawn_replica()
            print(
                f"replica spawned on group {srv._group_of[s]}; "
                f"{len(srv.servers)} replica(s) live",
                file=sys.stderr,
            )
        except (ValueError, RuntimeError) as e:
            print(f"spawn failed: {e}", file=sys.stderr)
    elif cmd == ":profile":
        sub = parts[1].split() if len(parts) > 1 else []
        if not sub:
            print("usage: :profile N [TRACE_DIR]", file=sys.stderr)
            return srv
        try:
            bundle = srv.stepline_capture(
                int(sub[0]), trace_dir=sub[1] if len(sub) > 1 else None
            )
        except ValueError as e:
            print(f"profile failed: {e}", file=sys.stderr)
            return srv
        print(json.dumps(bundle, sort_keys=True), file=sys.stderr)
    else:
        print(
            f"unknown control line {cmd!r} (dp daemon: :drain N, :spawn, "
            ":counters, :stats, :profile)",
            file=sys.stderr,
        )
    return srv


def cmd_serve(args) -> int:
    """Interactive persistent daemon: one prompt per stdin line, streamed
    completion per line (≙ the reference's forever-spinning worker loop).
    Lines starting with ``:`` are operator control commands — see
    ``_serve_control`` (hot repartition without restarting the daemon) and
    ``_dp_serve_control`` (replica drain/spawn on the dp daemon)."""
    from .runtime.server import QueueFull, RequestFailed, ServerClosed
    from .utils.device_report import device_report

    # fail an inconsistent set of flags in milliseconds, not after minutes
    # of model loading (PipelineServer makes the same call, but only once
    # the engine is up)
    try:
        options = _serve_options(args)
        options.validate()
    except ValueError as e:
        print(_flag_error(e), file=sys.stderr)
        return 2
    if options.cp > 1 and (
        getattr(args, "tensor_parallel", 1) > 1 or options.speculate
    ):
        # what the engine and the server refuse as not implemented
        print(
            "error: --cp with --tensor-parallel or --speculate is not "
            "supported yet",
            file=sys.stderr,
        )
        return 2
    if getattr(args, "tenants_config", None) and not getattr(
        args, "http_port", 0
    ):
        print(
            "error: --tenants-config needs --http-port (tenant policy is "
            "enforced at the HTTP ingress; stdin prompts have no tenant)",
            file=sys.stderr,
        )
        return 2
    if getattr(args, "autoscale", False) and getattr(
        args, "data_parallel", 1
    ) < 2:
        print(
            "error: --autoscale needs --data-parallel >= 2 (the autoscaler "
            "drives ReplicatedServer drain/spawn between --min-replicas "
            "and the replica count)",
            file=sys.stderr,
        )
        return 2
    # -- disaggregated serving flags: fail fast, before model load ---------
    disagg = getattr(args, "disagg", False)
    roles = None
    planner = None
    if (getattr(args, "prefill_replicas", 0) or getattr(args, "roles", None)
            or getattr(args, "profile_json", None)) and not disagg:
        print(
            "error: --prefill-replicas/--roles/--profile-json need --disagg",
            file=sys.stderr,
        )
        return 2
    if disagg:
        dp = getattr(args, "data_parallel", 1)
        if dp < 2:
            print(
                "error: --disagg needs --data-parallel >= 2 (prefill and "
                "decode pools each need at least one replica group)",
                file=sys.stderr,
            )
            return 2
        if not options.paged:
            print(
                "error: --disagg needs paged KV serving "
                "(--kv-block-size/--kv-blocks): the hand-off engine "
                "streams arena blocks between replicas",
                file=sys.stderr,
            )
            return 2
        if options.prefix_cache == "off":
            print(
                "error: --disagg needs --prefix-cache hbm, host or disk: the "
                "hand-off lands streamed KV in the decode replica's radix "
                "tree so adoption skips re-prefill",
                file=sys.stderr,
            )
            return 2
        if getattr(args, "prefill_replicas", 0) and getattr(
            args, "roles", None
        ):
            print(
                "error: --prefill-replicas and --roles are mutually "
                "exclusive",
                file=sys.stderr,
            )
            return 2
        if getattr(args, "prefill_replicas", 0) and not (
            1 <= args.prefill_replicas <= dp - 1
        ):
            print(
                f"error: --prefill-replicas must be in [1, "
                f"{dp - 1}] (both sides need at least one replica), got "
                f"{args.prefill_replicas}",
                file=sys.stderr,
            )
            return 2
        if getattr(args, "roles", None):
            roles = [r.strip() for r in args.roles.split(",")]
            from .obs.metrics import REPLICA_ROLES

            if len(roles) != dp or any(
                r not in REPLICA_ROLES for r in roles
            ):
                print(
                    f"error: --roles needs {dp} comma-separated values "
                    f"from {REPLICA_ROLES}, got {args.roles!r}",
                    file=sys.stderr,
                )
                return 2
        if getattr(args, "profile_json", None):
            from .runtime.placement import PlacementPlanner

            try:
                planner = PlacementPlanner.from_json(args.profile_json)
            except (OSError, ValueError, KeyError, TypeError) as e:
                print(f"error: bad --profile-json: {e}", file=sys.stderr)
                return 2
    if getattr(args, "tenants_config", None):
        # fail a malformed tenants file in milliseconds, not after model load
        from .runtime.fairness import load_tenants_config

        try:
            load_tenants_config(args.tenants_config)
        except (OSError, ValueError, TypeError, KeyError) as e:
            print(f"error: bad --tenants-config: {e}", file=sys.stderr)
            return 2
    # -- graceful SIGTERM: DRAINING -> finish in-flight -> exit 0 ----------
    # Installed BEFORE model build and the "serving" banner: the drain
    # contract must hold from the first moment a supervisor can observe the
    # daemon. The old install point sat after a lazy tokenizer probe whose
    # transformers import left a multi-second window where a SIGTERM racing
    # the banner still meant die-raw instead of drain.
    _term_evt = threading.Event()
    if threading.current_thread() is threading.main_thread():
        try:
            signal.signal(signal.SIGTERM, lambda *_: _term_evt.set())
        except (ValueError, OSError):
            pass  # embedded interpreter without signal support
    if getattr(args, "data_parallel", 1) > 1:
        # data-parallel daemon: D replica servers over disjoint device
        # groups behind a router (runtime/replicated.py). :placement is a
        # single-engine control — not offered here.
        if getattr(args, "restore", None):
            # refuse loudly rather than silently starting fresh: dp restore
            # needs one snapshot per replica (the API exists —
            # ReplicatedServer.snapshot / restore_into — but has no
            # single-directory CLI wiring yet)
            print(
                "--restore with --data-parallel is not supported from the "
                "CLI; use ReplicatedServer.snapshot/restore_into",
                file=sys.stderr,
            )
            return 2
        from .runtime.replicated import ReplicatedServer
        from .utils import shard_store

        cfg, params = shard_store.load_full(args.shards, dtype=_dtype(args.dtype))
        placement = _placement(args, cfg.num_hidden_layers)
        if disagg:
            from .runtime.disagg import DisaggServer

            cls = DisaggServer
            disagg_kw = dict(
                roles=roles,
                prefill_replicas=(
                    getattr(args, "prefill_replicas", 0) or
                    (1 if roles is None else None)
                ),
                planner=planner,
            )
        else:
            cls = ReplicatedServer
            disagg_kw = {}
        srv = cls(
            cfg, params,
            data_parallel=args.data_parallel,
            **disagg_kw,
            num_stages=None if placement else getattr(args, "stages", None),
            tensor_parallel=getattr(args, "tensor_parallel", 1),
            placement=placement,
            tokenizer=shard_store.load_tokenizer(args.shards),
            min_replicas=getattr(args, "min_replicas", 1),
            # (--cp: each replica's paged arena is sharded over cp chips of
            # its own device group — dp × cp × stages in all)
            **vars(options),
        )
        eng = srv.engines[0]
        extra = ""
        if disagg:
            extra = (
                " [disagg roles: "
                + ",".join(
                    srv.roles[d] for d in sorted(srv.roles)
                )
                + (", planner: profile.json fits" if planner is not None
                   else ", planner: none (load routing)")
                + "]"
            )
        print(
            f"serving {eng.cfg.model_type}: {args.data_parallel} replicas x "
            f"{eng.mesh.shape} (capacity={args.capacity}){extra}; enter a "
            "prompt, ^D to exit; :drain N / :spawn resize the replica set "
            "live",
            file=sys.stderr,
        )
    else:
        eng = _engine(args)
        if getattr(args, "restore", None):
            # resume a snapshotted daemon: in-flight requests continue
            # token-exactly from where the snapshot left them
            from .runtime.server import PipelineServer, load_snapshot

            srv = PipelineServer.restore(eng, load_snapshot(args.restore))
            if args.snapshot_every or args.snapshot_dir:
                # ops knobs never ride in the snapshot's serve_kwargs — the
                # revived daemon re-arms auto-snapshot from the CLI flags
                srv.enable_auto_snapshot(
                    args.snapshot_dir, args.snapshot_every or None
                )
            if args.trace_path:
                # the snapshot's serve_kwargs never carry observability
                # knobs — attach the trace to the revived daemon directly
                from .obs.trace import TraceWriter

                srv._trace = TraceWriter(args.trace_path)
            revived = [
                r for r in srv._rows if r is not None and not r.done
            ] + [r for r in srv._queue]
            print(
                f"restored snapshot from {args.restore}: "
                f"{len(revived)} live request(s) resume",
                file=sys.stderr,
            )
            # the snapshot's serve_kwargs win over the CLI serve flags —
            # say so explicitly instead of silently ignoring them (the old
            # banner printed the CLI --capacity while the daemon actually
            # ran at the snapshot's; ADVICE r5)
            flag_of = dict(_serve_flags())
            ignored = [
                f"--{flag_of[name].replace('_', '-')} "
                f"{getattr(options, name)} (snapshot: {used})"
                for name, used in srv.options.portable().items()
                if hasattr(args, flag_of[name])
                and getattr(options, name) != used
            ]
            if ignored:
                print(
                    "warning: serve flags differ from the snapshot and are "
                    "ignored (a restored daemon keeps its snapshot's "
                    "serve_kwargs): " + ", ".join(ignored),
                    file=sys.stderr,
                )
            if revived:
                # finish the snapshot's requests first; their clients are
                # gone, so the completed text goes to stdout one per line
                srv.run_until_idle()
                t = eng._require_tokenizer()
                for r in revived:
                    print(t.decode(r.tokens, skip_special_tokens=True),
                          flush=True)
        else:
            srv = eng.serve(**vars(options))
        # srv.capacity, not args.capacity: after --restore the daemon runs
        # at the SNAPSHOT's serve_kwargs (ADVICE r5 — the banner used to
        # claim the CLI value)
        print(
            f"serving {eng.cfg.model_type} over {eng.mesh.shape} "
            f"(capacity={srv.capacity}); enter a prompt, ^D to exit; "
            f":placement <ranges|N> re-shards live",
            file=sys.stderr,
        )
    ingress = None
    autoscaler = None
    metrics_srv = _start_metrics(
        getattr(args, "metrics_port", 0),
        # late-bound: ``srv`` is rebound on :placement — the provider always
        # reads the CURRENT server's tally (dp routers expose per-replica
        # load too)
        statz_extra={
            "counters": lambda: srv.counters.snapshot(),
            # step-profiler aggregates (host occupancy, p50 step wall;
            # per-replica on dp routers)
            "stepline": lambda: srv.stepline_stats(),
            # the device line, package versions, per-device memory and
            # the compile cache in use (utils/device_report.py)
            "device": device_report,
            **(
                {"replicas": lambda: srv.stats()["replicas"]}
                if getattr(args, "data_parallel", 1) > 1 else {}
            ),
        },
        # /healthz now answers from the LIVE state machine: 503 on
        # DEGRADED/DRAINING (and on an ingress-level drain) so a load
        # balancer rotates the daemon out
        health=lambda: ingress.health if ingress is not None else srv.health,
        # /profilez deep capture: None steps = ring view, N = arm + wait.
        # Late-bound like the rest — :placement rebinds ``srv``.
        profilez=lambda steps, wait_s: (
            srv.stepline_capture(steps, wait_s) if steps is not None
            else {
                "stepline": srv.stepline_stats(),
                "steps": srv.stepline_snapshot(64),
            }
        ),
    )
    # a tokenizer-less store still serves: the HTTP ingress speaks token
    # ids and stdin prompts get a per-line refusal instead of a dead daemon
    try:
        tok = eng._require_tokenizer()
    except ValueError:
        tok = None
    # -- production ingress: HTTP/SSE front door + fairness + autoscale ----
    if getattr(args, "http_port", 0):
        from .runtime.ingress import start_ingress

        ingress = start_ingress(
            srv,
            port=args.http_port,
            tokenizer=tok,
            tenants=getattr(args, "tenants_config", None),
            max_queue=args.max_queue or None,
            model_name=eng.cfg.model_type,
            # the trace ROOT spans (ingress + fair-queue wait) land in
            # PATH.ingress; trace-report merges them with the per-replica
            # files into one tree per request
            trace_path=args.trace_path,
            on_error=lambda msg: print(msg, file=sys.stderr),
        )
        if ingress is not None:
            print(
                f"ingress: http://127.0.0.1:{ingress.port}/v1/completions "
                f"(tenants: {', '.join(ingress.fair.tenants())})",
                file=sys.stderr,
            )
    if getattr(args, "autoscale", False):
        from .runtime.autoscale import Autoscaler

        autoscaler = Autoscaler(
            srv,
            min_replicas=getattr(args, "min_replicas", 1),
            scale_up_load=getattr(args, "autoscale_up_load", 0.8),
            scale_down_load=getattr(args, "autoscale_down_load", 0.3),
            up_after_s=getattr(args, "autoscale_up_after", 1.0),
            down_after_s=getattr(args, "autoscale_down_after", 5.0),
            cooldown_s=getattr(args, "autoscale_cooldown", 3.0),
            # paced role rebalance: only a --disagg router with a
            # --profile-json planner acts on it (a no-op otherwise)
            rebalance_every_s=getattr(args, "rebalance_every", 30.0),
            extra_load=(
                (lambda: ingress.fair.depth()) if ingress is not None
                else None
            ),
        )
        if ingress is not None:
            # the ingress ticks the controller from its sidecar thread,
            # with the fair-queue backlog folded into the load signal
            ingress.attach_autoscaler(autoscaler)
        else:
            # no HTTP front door: tick from a sidecar thread so the dp
            # daemon still self-sizes under Python-API / stdin load
            def _tick_forever():
                while not _term_evt.is_set():
                    try:
                        autoscaler.tick()
                    except Exception as e:  # noqa: BLE001 — policy errors
                        # must never kill the daemon
                        print(f"autoscale tick failed: {e}", file=sys.stderr)
                    time.sleep(0.25)

            threading.Thread(
                target=_tick_forever, daemon=True, name="autoscale-tick"
            ).start()
        print(
            f"autoscale: replicas in [{autoscaler.min_replicas}, "
            f"{autoscaler.max_replicas}], up at load >= "
            f"{autoscaler.scale_up_load:g}, down at <= "
            f"{autoscaler.scale_down_load:g}",
            file=sys.stderr,
        )
    n_prompt = 0
    for line in _stdin_lines(_term_evt):
        prompt = line.rstrip("\n")
        if not prompt:
            continue
        if prompt.startswith(":"):
            if getattr(args, "data_parallel", 1) > 1:
                srv = _dp_serve_control(srv, prompt)
            else:
                # freeze dispatch/stepping ONLY for the :placement rebuild:
                # the old server is drained, re-sharded and closed — a pump
                # racing that would submit to (and step) a server whose
                # arrays are being swapped under it. Queued HTTP requests
                # simply wait out the maintenance window. Read-only controls
                # must NOT pause: ``:profile N`` waits for the pump to fill
                # its capture window — pausing it would freeze the very
                # steps it measures (the bundle came back empty).
                freeze = ingress is not None and prompt.split()[0] == ":placement"
                if freeze:
                    ingress.pause()
                try:
                    srv = _serve_control(eng, srv, prompt, args)
                finally:
                    if freeze:
                        if ingress.backend is not srv:
                            # the rebuild produced a new server — point
                            # the front door at the live one
                            ingress.backend = srv
                        ingress.resume()
            continue
        if tok is None:
            print(
                "rejected: this store has no tokenizer — text prompts "
                "need one (the HTTP ingress still accepts token-id "
                "prompts)",
                file=sys.stderr,
            )
            continue
        ids = np.asarray(tok(prompt)["input_ids"], np.int32)
        # per-request seed advances from --seed so two identical sampled
        # prompts in one session draw different completions (ADVICE r3 #3)
        try:
            req = srv.submit(
                ids, args.max_new, temperature=args.temperature,
                seed=args.seed + n_prompt, stop=args.stop,
            )
        except (QueueFull, ServerClosed, ValueError) as e:
            # backpressure and bad requests (prompt too long for the model,
            # over-capacity max_new) are NORMAL answers, not crashes:
            # report the rejection and keep the daemon reading prompts
            print(f"rejected: {e}", file=sys.stderr)
            continue
        n_prompt += 1
        acc: list[int] = []
        prev = ""
        try:
            for t in srv.stream(req):
                acc.append(t)
                text = tok.decode(acc, skip_special_tokens=True)
                if len(text) > len(prev) and not text.endswith("�"):
                    print(text[len(prev):], end="", flush=True)
                    prev = text
        except RequestFailed as e:
            # deadline expiry / contained failure: the partial completion
            # already streamed; name the cause and keep serving
            print(f"\n[request failed: {e.__cause__ or e}]", file=sys.stderr)
        print(flush=True)
    if _term_evt.is_set():
        # k8s-style rolling restart: SIGTERM means drain, not die. New
        # work is shed with 503 (ingress DRAINING; /healthz pulls us from
        # rotation), in-flight requests FINISH (the ingress pump keeps
        # stepping its streams to completion), an armed snapshot dir gets
        # a final checkpoint, and the exit code is 0 — no live stream is
        # ever killed by a restart again.
        print("SIGTERM: draining (new requests shed with 503)",
              file=sys.stderr)
        if ingress is not None:
            ingress.begin_drain()
        try:
            srv.run_until_idle()  # finish in-flight requests
        except Exception as e:  # noqa: BLE001 — drain anyway
            print(f"drain pump failed: {e}", file=sys.stderr)
        if ingress is not None and not ingress.wait_idle(
            timeout_s=getattr(args, "drain_grace", 60.0)
        ):
            # report the truncation honestly instead of claiming a clean
            # drain — the exit code stays 0 (k8s sends SIGKILL next
            # anyway; dying mid-sentence loudly beats dying silently)
            print(
                "warning: drain grace expired with streams still live — "
                "raise --drain-grace to let long completions finish",
                file=sys.stderr,
            )
        if (
            args.snapshot_dir and getattr(args, "data_parallel", 1) == 1
            and hasattr(srv, "snapshot")
        ):
            try:
                from .runtime.server import save_snapshot

                save_snapshot(srv.snapshot(), args.snapshot_dir)
                print(f"final snapshot written to {args.snapshot_dir}",
                      file=sys.stderr)
            except Exception as e:  # noqa: BLE001 — a failed final
                # snapshot must not turn a graceful drain into rc != 0
                print(f"final snapshot failed: {e}", file=sys.stderr)
        print("drained; exiting 0", file=sys.stderr)
    print(json.dumps(srv.counters.snapshot()), file=sys.stderr)
    if ingress is not None:
        ingress.stop()
    if metrics_srv is not None:
        metrics_srv.stop()
    if hasattr(srv, "close"):
        srv.close()  # flush the JSONL trace
    return 0


def _start_metrics(port, statz_extra=None, health=None, profilez=None):
    """Start the background ``/metrics`` + ``/statz`` exposition thread when
    a port is requested (0/None = disabled). Returns the MetricsServer or
    None. Bind failures (port taken) are reported and non-fatal — the daemon
    serves without exposition rather than dying. ``health`` (a zero-arg
    callable returning the state name) makes ``/healthz`` answer 503 unless
    the state is SERVING. ``profilez`` (``fn(steps, wait_s)``) wires the
    live server's step-profiler capture into ``/profilez``."""
    if not port:
        return None
    from .obs.http import MetricsServer

    try:
        ms = MetricsServer(
            port=port, statz_extra=statz_extra, health_provider=health
        )
        if profilez is not None:
            ms.set_profilez_provider(profilez)
        ms.start()
    except OSError as e:
        print(f"metrics endpoint disabled: {e}", file=sys.stderr)
        return None
    print(
        f"metrics: http://127.0.0.1:{ms.port}/metrics (Prometheus), "
        f"/statz (JSON), /profilez (step capture)",
        file=sys.stderr,
    )
    return ms


def cmd_worker(args) -> int:
    """One multi-controller process (≙ ``start_node.py`` — one OS process per
    node, ``/root/reference/start_node.py:6-20``): joins the cluster, builds
    the engine over the GLOBAL mesh, and runs the same SPMD program as every
    other worker. Process 0 speaks for the job."""
    import os

    if args.local_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.local_devices}"
        )
    # must precede ANY backend use (see parallel/distributed.py)
    from .parallel.distributed import initialize_multihost

    initialize_multihost(args.coordinator, args.processes, args.process_id)
    import jax

    from .utils.compile_cache import enable_persistent_cache

    enable_persistent_cache(jax.devices()[0].platform)
    print(
        f"[worker {args.process_id}] joined: {jax.process_count()} processes, "
        f"{jax.device_count()} global devices",
        file=sys.stderr,
    )
    # per-process exposition: base port + process id (every worker is its
    # own scrape target, ≙ the reference's per-node logs but queryable)
    metrics_srv = _start_metrics(
        args.metrics_port + args.process_id if args.metrics_port else 0
    )
    eng = _engine(args)
    text = eng.generate_text(args.prompt, args.max_new)
    if args.process_id == 0:
        print(text)
    if metrics_srv is not None:
        metrics_srv.stop()
    return 0


def cmd_launch(args) -> int:
    """Spawn N worker processes on this host and wait (≙ ``run_this.sh:8-17``
    spawning per-node ``start_node.py`` daemons with per-node logs). Each
    worker joins the jax.distributed cluster and runs the same pipelined
    program over the global mesh; worker 0's completion goes to stdout, and
    every worker's output is kept in ``worker_<i>.log`` (≙ ``node_<port>.log``).

    This is a CPU SIMULATION of a pod: every worker runs on virtual CPU
    devices. This system drives all chips of a host from ONE process, and a
    real pod runs ``worker`` directly — one per host, with ``--coordinator
    host0:port``; several local processes sharing one host's chips would
    need per-process chip visibility that nothing here sets. The parent
    never initialises a backend (``main`` routes ``launch`` around it)."""
    import contextlib
    import os
    import socket
    import subprocess
    import time

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # --local-devices sizes each worker's mesh
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        + [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
    )

    os.makedirs(args.log_dir, exist_ok=True)
    rc = 0
    with contextlib.ExitStack() as stack:
        procs: list = []
        logs: list[str] = []
        for pid in range(args.processes):
            cmd = [
                sys.executable, "-m", "llm_sharding_tpu", "worker",
                args.shards,
                "--coordinator", f"localhost:{port}",
                "--processes", str(args.processes),
                "--process-id", str(pid),
                "--prompt", args.prompt,
                "--max-new", str(args.max_new),
                "--dtype", args.dtype,
            ]
            if args.stages:
                cmd += ["--stages", str(args.stages)]
            if args.ranges:
                cmd += ["--ranges", args.ranges]
            if args.local_devices:
                cmd += ["--local-devices", str(args.local_devices)]
            if getattr(args, "metrics_port", 0):
                # base port; each worker binds base + its process id
                cmd += ["--metrics-port", str(args.metrics_port)]
            log_path = os.path.join(args.log_dir, f"worker_{pid}.log")
            logs.append(log_path)
            log = stack.enter_context(open(log_path, "w"))
            p = subprocess.Popen(
                cmd,
                stdout=subprocess.PIPE if pid == 0 else log,
                stderr=log,
                text=True,
                env=env,
            )
            stack.callback(lambda p=p: p.poll() is None and p.kill())
            procs.append(p)

        # drain worker 0's stdout concurrently: a completion larger than the
        # OS pipe buffer would otherwise block the worker forever
        import threading

        out0_parts: list[str] = []
        drain0 = threading.Thread(
            target=lambda: out0_parts.append(procs[0].stdout.read()),
            daemon=True,
        )
        drain0.start()

        # Watchdog (≙ the reference's operator tailing node logs,
        # run_this.sh:20-22 — but automated): one worker dying would leave
        # the rest blocked in collectives until the coordination-service
        # timeout, so kill the job as soon as any worker fails, and bound
        # the whole launch with --timeout.
        deadline = time.monotonic() + args.timeout if args.timeout else None
        failed = None
        while any(p.poll() is None for p in procs):
            for pid, p in enumerate(procs):
                if p.poll() is not None and p.returncode != 0:
                    failed = (pid, p.returncode)
                    break
            if failed or (deadline and time.monotonic() > deadline):
                for p in procs:
                    if p.poll() is None:
                        p.terminate()
                if failed is None:
                    failed = (-1, 124)
                    print(
                        f"launch timed out after {args.timeout}s; workers "
                        "terminated",
                        file=sys.stderr,
                    )
                break
            time.sleep(0.2)
        for pid, p in enumerate(procs):
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            if p.returncode != 0:
                rc = rc or p.returncode or 1
                print(
                    f"worker {pid} exited {p.returncode}; see {logs[pid]}",
                    file=sys.stderr,
                )
        drain0.join(timeout=10)
        if out0_parts and out0_parts[0]:
            print(out0_parts[0], end="")
    return rc


def cmd_profile(args) -> int:
    import jax
    import jax.numpy as jnp

    from .profiler.artifacts import save_profile_artifacts
    from .profiler.profiler import (
        Profiler, detect_hbm_bytes, max_layers_fit, measure_hop_latency,
        profile_cold_start,
    )

    dtype = _dtype(args.dtype)
    cold = None
    if args.shards:
        from .utils import shard_store

        cfg, params = shard_store.load_full(args.shards, dtype=dtype)
        # the profiler times a monolithic forward: one placement up front
        # (load_full returns host arrays — numpy params passed to a jitted
        # step would be re-uploaded inside every timed call)
        params = jax.device_put(params)
        if args.cold_start:
            cold = profile_cold_start(args.shards, dtype=dtype)
    else:
        from .models import config as config_mod

        from .models.family import family

        cfg = getattr(config_mod, args.preset)()
        fam = family(cfg)
        # the Profiler times the whole-model forward and sizes a layer's
        # cache as K/V heads × head_dim: not layers of several kinds, not a
        # latent entry
        if fam.forward is None or cfg.layer_kinds or cfg.latent_kv:
            raise SystemExit(
                f"preset {args.preset!r} has unsupported model_type "
                f"{cfg.model_type!r} for random-weight profiling"
            )
        params = fam.init_params(cfg, jax.random.key(0), dtype=dtype)

    prof = Profiler(cfg, params, dtype=dtype)
    prefill = prof.profile_prefill()
    decode = prof.profile_decode(max_tokens=args.decode_tokens)
    verdict = Profiler.similarity_verdict(prefill, decode)

    hop = None
    if args.hops:
        from .parallel.mesh import pipeline_mesh

        n = min(args.hops, len(jax.devices()))
        hop = measure_hop_latency(
            pipeline_mesh(n), hidden_size=cfg.hidden_size, dtype=dtype
        )

    extra = {"config": json.loads(cfg.to_json())}
    # Memory fit is only reportable when device memory is determinable: an
    # explicit --hbm-gib, runtime memory_stats, or a known TPU kind. On CPU
    # hosts (like the reference profiler running wherever it's pointed,
    # node_profiler.py:300-308) the field is omitted rather than guessed.
    hbm = int(args.hbm_gib * 1024**3) if args.hbm_gib else detect_hbm_bytes()
    if hbm is not None:
        extra["max_layers_fit"] = max_layers_fit(
            cfg, param_dtype=dtype, hbm_bytes=hbm
        )
    if args.suggest_stages:
        from .parallel.placement import PlacementSpec

        # homogeneous chips: per-stage capability = 1/c_k each; shown so the
        # operator sees the profiler→placement loop end to end
        spec = PlacementSpec.from_capabilities(
            cfg.num_hidden_layers, [1.0 / prefill.capability_c_k] * args.suggest_stages
        )
        extra["suggested_placement"] = list(spec.stages)

    payload = save_profile_artifacts(
        args.out, prefill=prefill, decode=decode, verdict=verdict,
        cold_start=cold, hop=hop, extra=extra,
    )
    print(json.dumps(payload, indent=2))
    print(f"artifacts -> {args.out}", file=sys.stderr)
    return 0


def cmd_trace_report(args) -> int:
    """Merge per-replica/ingress/router JSONL trace files, rebuild the
    cross-replica span trees, and print per-phase latency attribution
    (see obs/report.py). Runs jax-free — point it at the files wherever
    they landed."""
    import glob as _glob

    from .obs.report import (
        load_events, render_report, report_json, trace_json,
    )

    paths = []
    for pat in args.files:
        hits = sorted(_glob.glob(pat)) if any(
            c in pat for c in "*?[") else [pat]
        paths.extend(hits)
    if not paths:
        print("no trace files matched", file=sys.stderr)
        return 2
    try:
        events = load_events(paths)
    except OSError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if not events:
        print("no span events in the input files", file=sys.stderr)
        return 1
    if args.json and args.trace is not None:
        out = trace_json(events, args.trace)
        print(json.dumps(out, sort_keys=True))
        return 0 if out["found"] else 1
    if args.json:
        print(json.dumps(report_json(events, top=args.top), sort_keys=True))
    else:
        print(render_report(events, top=args.top, trace_id=args.trace))
    return 0


def cmd_step_report(args) -> int:
    """Render step-profiler captures offline: merge ``/profilez`` bundles,
    ``/debugz`` postmortems and raw ``:profile`` dumps into the per-phase
    host-time attribution, occupancy timeline and worst device bubbles
    (see obs/report.py). Runs jax-free — point it at the JSON files
    wherever they landed."""
    import glob as _glob

    from .obs.report import (
        load_steps, render_step_report, step_report_json,
    )

    paths = []
    for pat in args.files:
        hits = sorted(_glob.glob(pat)) if any(
            c in pat for c in "*?[") else [pat]
        paths.extend(hits)
    paths = [p for p in paths if os.path.exists(p)]
    if not paths:
        print("no capture files matched", file=sys.stderr)
        return 2
    steps = load_steps(paths)
    if not steps:
        print("no step records in the input files", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(step_report_json(steps, top=args.top),
                         sort_keys=True))
    else:
        print(render_step_report(steps, top=args.top))
    return 0


def cmd_lint(args) -> int:
    """shardlint: the repo-native static-analysis pass (jax-free — see
    ``analysis/``). Exits nonzero on findings not in the baseline."""
    from .analysis.core import run_lint

    return run_lint(
        only=args.rule or None,
        baseline_path=args.baseline,
        as_json=args.json,
        write_baseline=args.write_baseline,
    )


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m llm_sharding_tpu",
        description="TPU-native model-chain framework — operator commands",
    )
    p.add_argument("-v", "--verbose", action="store_true")
    sub = p.add_subparsers(dest="command", required=True)

    c = sub.add_parser("convert", help="HF checkpoint dir -> shard store")
    c.add_argument("model_dir")
    c.add_argument("out_dir")
    c.add_argument("--dtype", default="bf16")
    c.add_argument(
        "--quantize-head", action="store_true", dest="quantize_head",
        help="with --dtype int8/int4: also quantize the vocab tables (embed "
        "per-row scales, untied lm_head per-column) — the tied table is "
        "~20%% of per-step weight reads at llama-3 geometry",
    )
    c.set_defaults(fn=cmd_convert)

    g = sub.add_parser("generate", help="run one prompt through the pipeline")
    g.add_argument("shards")
    g.add_argument("--prompt", required=True)
    g.add_argument("--max-new", type=int, default=128, dest="max_new")
    g.add_argument("--stages", type=int)
    g.add_argument("--ranges", help="ragged layer ranges, e.g. 0:6,6:7,7:32")
    g.add_argument("--dtype", default="bf16")
    g.add_argument("--stream", action="store_true")
    g.add_argument("--temperature", type=float, default=0.0)
    g.add_argument("--top-k", type=int, default=0, dest="top_k")
    g.add_argument("--top-p", type=float, default=1.0, dest="top_p")
    g.add_argument("--seed", type=int, default=0)
    g.set_defaults(fn=cmd_generate)

    s = sub.add_parser("serve", help="persistent stdin daemon (streaming)")
    s.add_argument("shards")
    s.add_argument("--max-new", type=int, default=256, dest="max_new")
    s.add_argument("--stages", type=int)
    s.add_argument("--ranges")
    s.add_argument("--capacity", type=int, default=1024)
    s.add_argument("--batch-per-slot", type=int, default=1, dest="batch_per_slot")
    s.add_argument(
        "--data-parallel", type=int, default=1, dest="data_parallel",
        help="serve N independent pipeline replicas over disjoint device "
        "groups behind a least-loaded router (runtime/replicated.py)",
    )
    s.add_argument(
        "--tensor-parallel", type=int, default=1, dest="tensor_parallel",
        help="megatron tensor parallelism per pipeline (composes with "
        "--stages and --data-parallel: devices = dp x stages x tp)",
    )
    s.add_argument(
        "--cp", type=int, default=1,
        help="context parallelism for long-context serving (with "
        "--kv-block-size/--kv-blocks): shard the paged KV arena across N "
        "chip groups so the admissible context grows ~N-fold at fixed "
        "per-chip HBM (devices = cp x stages; with --data-parallel, dp x "
        "cp x stages). Chunked prefill runs ring passes over "
        "shard-resident KV and decode combines per-shard attention "
        "partials; greedy output stays token-identical to cp=1. Composes "
        "with snapshots, migration/failover, --disagg and the host "
        "prefix tier (per-shard block streaming)",
    )
    s.add_argument(
        "--min-replicas", type=int, default=1, dest="min_replicas",
        help="with --data-parallel: refuse ':drain N' (and report it) when "
        "fewer than this many replicas would remain live — the elasticity "
        "floor of the replica supervision layer",
    )
    s.add_argument(
        "--prefill-chunk", type=int, default=None, dest="prefill_chunk",
        help="prefill prompts longer than this in bounded chunks so live "
        "streams keep producing during admission (power of two). Paged "
        "chunks attend the pooled arena in place (--paged-attn governs "
        "the kernel) and COMPOSE with the radix prefix cache: a cached "
        "hit's leftover suffix chunk-prefills from its offset instead "
        "of falling back cold",
    )
    s.add_argument(
        "--speculate", type=int, default=0,
        help="speculative decoding: draft up to K tokens per row by n-gram "
        "lookup over the request's own ids and verify K+1 positions per "
        "forward — greedy output is token-identical, decode tok/s rises "
        "with the workload's self-repetition (0 = off; incompatible with "
        "--prefill-chunk)",
    )
    s.add_argument(
        "--spec-ngram", type=int, default=3, dest="spec_ngram",
        help="longest n-gram the drafter matches against the request's "
        "prompt+generation suffix (with --speculate)",
    )
    s.add_argument("--dtype", default="bf16")
    s.add_argument("--temperature", type=float, default=0.0)
    s.add_argument(
        "--seed", type=int, default=0,
        help="base sampling seed; each submitted prompt advances it by one",
    )
    s.add_argument("--top-k", type=int, default=0, dest="top_k")
    s.add_argument("--top-p", type=float, default=1.0, dest="top_p")
    s.add_argument(
        "--stop", action="append", default=None,
        help="stop string (repeatable): generation ends when the decoded "
        "text contains it",
    )
    s.add_argument(
        "--max-queue", type=int, default=0, dest="max_queue",
        help="admission control: reject submits (QueueFull) once this many "
        "requests are waiting for a slot (0 = unbounded) — backpressure "
        "instead of an ever-growing backlog in front of a saturated device",
    )
    s.add_argument(
        "--default-deadline", type=float, default=0.0,
        dest="default_deadline",
        help="default per-request deadline in seconds from submission "
        "(0 = none): still queued past it -> shed at admit time; "
        "mid-decode past it -> cancelled at the next chunk boundary",
    )
    s.add_argument(
        "--kv-block-size", type=int, default=0, dest="kv_block_size",
        help="paged KV serving: tokens per arena block (power of two, e.g. "
        "64). With --kv-blocks, replaces the per-row dense cache "
        "reservation with a pooled block arena + per-request block tables "
        "(PagedAttention): HBM scales with tokens actually in flight, "
        "shared prefixes are stored once, greedy output stays "
        "token-identical to dense (0 = dense mode, the default)",
    )
    s.add_argument(
        "--kv-blocks", type=int, default=0, dest="kv_blocks",
        help="paged KV serving: total arena blocks (>= 2; block 0 is the "
        "reserved trash sink). KV HBM per stage is roughly kv-blocks x "
        "kv-block-size x Nkv x Dh x 2 x dtype-bytes x layers-per-stage; "
        "admission waits in queue when free blocks run out",
    )
    s.add_argument(
        "--kv-dtype", choices=("bf16", "int8", "fp8"), default="bf16",
        dest="kv_dtype",
        help="paged KV arena storage dtype (with --kv-block-size/"
        "--kv-blocks): bf16 = store in the compute cache dtype (exact, "
        "the default); int8/fp8 = 1-byte codes with per-block-per-head "
        "scales, dequantized inside the paged-attention kernel's "
        "per-block DMA loop — ~2x the arena blocks at equal HBM (and 2x "
        "the radix/host-tier capacity) and half the decode-attention "
        "bandwidth, at a small bounded greedy-token drift (gate rollouts "
        "on the benchmark's correctness check; bf16 stays default). "
        "int8 with --paged-attn kernel wants --kv-block-size a multiple "
        "of 32 (1-byte Mosaic sublane)",
    )
    s.add_argument(
        "--paged-attn", choices=("auto", "kernel", "xla"), default="auto",
        dest="paged_attn",
        help="paged attention implementation for BOTH decode steps and "
        "chunked prefill (with --kv-block-size/--kv-blocks): auto = "
        "Pallas kernels on TPU for Mosaic-eligible shapes (head_dim %% "
        "128 == 0, block size a sublane multiple), exact XLA gather "
        "elsewhere; kernel = require the Pallas kernels (fails at "
        "startup if ineligible); xla = force the gather fallback. The "
        "decode kernel streams only each row's mapped blocks per step "
        "(a cell of several at a time, copied by its body into a double "
        "buffer — the cell's width follows from the shapes); the "
        "chunked-prefill kernel "
        "(--prefill-chunk) attends the arena in place up to each row's "
        "written frontier, so admission never round-trips a gathered "
        "window through HBM",
    )
    s.add_argument(
        "--prefix-cache", choices=("off", "hbm", "host", "disk"),
        default="off", dest="prefix_cache",
        help="automatic prefix caching (with --kv-block-size/--kv-blocks): "
        "a radix tree over token ids indexes every finished request's "
        "prompt blocks, and every new request transparently reuses its "
        "longest cached prefix (system prompts, few-shot preambles, "
        "multi-turn chat history) with zero caller coordination — greedy "
        "output stays token-identical to the cold path. hbm = cache lives "
        "in the device arena and cold entries drop under pressure; host = "
        "cold entries first demote to a pinned host-RAM pool and stream "
        "back on a later hit, so HBM becomes a cache level instead of a "
        "hard ceiling; disk = cold HOST entries further demote to "
        "memory-mapped files under --disk-pool-dir, survive restarts, and "
        "promote disk -> host -> arena on a hit. Explicit prefill_prefix "
        "handles remain the manual/pinned escape hatch",
    )
    s.add_argument(
        "--host-pool-blocks", type=int, default=0, dest="host_pool_blocks",
        help="host-RAM tier size in KV blocks for --prefix-cache host/disk "
        "(0 = default to --kv-blocks, an arena-sized pool); host RAM cost "
        "is pool x the per-block KV bytes",
    )
    s.add_argument(
        "--disk-pool-dir", default=None, dest="disk_pool_dir",
        help="directory for the --prefix-cache disk KV pool (required with "
        "disk mode); the pool is the persistent artifact — a restarted "
        "daemon re-adopts its entries cold, and snapshots reference them "
        "instead of inlining the KV bytes. With --data-parallel each "
        "replica pools under DIR/r<i>",
    )
    s.add_argument(
        "--disk-pool-blocks", type=int, default=0, dest="disk_pool_blocks",
        help="disk tier size in KV blocks for --prefix-cache disk (0 = "
        "default to --kv-blocks); disk cost is pool x the per-block KV "
        "bytes, per replica",
    )
    s.add_argument(
        "--snapshot-every", type=float, default=0.0, dest="snapshot_every",
        help="auto-checkpoint the live daemon at most every N seconds "
        "(atomic tmp+rename into --snapshot-dir; 0 = off); crash recovery "
        "is 'serve --restore SNAPSHOT_DIR'",
    )
    s.add_argument(
        "--snapshot-dir", default=None, dest="snapshot_dir",
        help="directory for --snapshot-every checkpoints (with "
        "--data-parallel each replica writes DIR.r<i>)",
    )
    s.add_argument(
        "--restore", default=None,
        help="resume a ':snapshot DIR' checkpoint: device serve state + "
        "in-flight/queued requests continue token-exactly (placement and "
        "shards must match the snapshotting daemon's)",
    )
    s.add_argument(
        "--metrics-port", type=int, default=0, dest="metrics_port",
        help="serve /metrics (Prometheus text) and /statz (JSON with "
        "p50/p90/p99 TTFT, queue-wait, inter-token latency) on "
        "127.0.0.1:PORT from a background thread (0 = off)",
    )
    s.add_argument(
        "--http-port", type=int, default=0, dest="http_port",
        help="production ingress: serve an OpenAI-compatible POST "
        "/v1/completions (SSE streaming with \"stream\": true, "
        "X-Deadline-Ms -> per-request deadline, request ids tied to the "
        "trace spans) on 127.0.0.1:PORT, with per-tenant rate limits and "
        "weighted fair queueing in front of admission (0 = off). Overload "
        "is shed EARLY with typed 429/503 + Retry-After; a client "
        "disconnect mid-stream cancels the row and frees its KV blocks",
    )
    s.add_argument(
        "--tenants-config", default=None, dest="tenants_config",
        help="JSON tenant policy for --http-port: {\"tenants\": {NAME: "
        "{\"key\": BEARER, \"weight\": W, \"rate_rps\": R, \"burst\": B, "
        "\"max_queued\": Q}}, \"allow_anonymous\": bool}. Without it every "
        "request lands on one unlimited anonymous tenant",
    )
    s.add_argument(
        "--autoscale", action="store_true",
        help="with --data-parallel: drive ReplicatedServer drain/spawn "
        "from the live load signal (backend queue + in-flight + ingress "
        "backlog over live slots) with hysteresis, between --min-replicas "
        "and the full replica count — the dp daemon self-sizes under a "
        "diurnal load curve instead of being hand-drained",
    )
    s.add_argument(
        "--autoscale-up-load", type=float, default=0.8,
        dest="autoscale_up_load",
        help="spawn a replica when the load signal holds at or above this "
        "for the sustain window (default 0.8)",
    )
    s.add_argument(
        "--autoscale-down-load", type=float, default=0.3,
        dest="autoscale_down_load",
        help="drain the least-loaded replica when the load signal holds "
        "at or below this for the (longer) sustain window (default 0.3)",
    )
    s.add_argument(
        "--drain-grace", type=float, default=60.0, dest="drain_grace",
        help="seconds a SIGTERM drain waits for live HTTP streams to "
        "finish before exiting (default 60; size it under the pod's "
        "terminationGracePeriod)",
    )
    s.add_argument(
        "--autoscale-up-after", type=float, default=1.0,
        dest="autoscale_up_after",
        help="seconds the high-load signal must SUSTAIN before a spawn "
        "(default 1.0) — short, because under-capacity sheds user traffic",
    )
    s.add_argument(
        "--autoscale-down-after", type=float, default=5.0,
        dest="autoscale_down_after",
        help="seconds the low-load signal must sustain before a drain "
        "(default 5.0) — longer than the up window, because over-capacity "
        "only wastes a device group",
    )
    s.add_argument(
        "--autoscale-cooldown", type=float, default=3.0,
        dest="autoscale_cooldown",
        help="seconds after any scale action during which the autoscaler "
        "only observes (default 3.0) — the churn guard",
    )
    s.add_argument(
        "--trace-path", default=None, dest="trace_path",
        help="append one JSONL line per span to this file for offline "
        "analysis (rotated at 64 MiB to PATH.1). Every span carries a "
        "trace_id, so 'trace-report PATH*' rebuilds per-request trees "
        "across files; with --data-parallel each replica writes PATH.r<i> "
        "plus PATH.router for hand-off/failover decisions, and --http-port "
        "adds PATH.ingress for the HTTP root spans",
    )
    s.add_argument(
        "--gauge-sweep-every", type=float, default=0.0,
        dest="gauge_sweep_every",
        help="pace the per-step load-gauge sweep (KV/radix occupancy, "
        "queue depths) to at most once per SECONDS of wall time, instead "
        "of every step (default 0.0 = every step, the historical "
        "behavior). The submit-path sweep is never paced — enqueue-time "
        "gauges stay fresh",
    )
    s.add_argument(
        "--rebalance-every", type=float, default=30.0,
        dest="rebalance_every",
        help="with --autoscale --disagg --profile-json: seconds between "
        "paced prefill:decode role-rebalance attempts "
        "(DisaggServer.rebalance — one role flip max per tick, riding the "
        "drain/spawn path; 0 = operator-only)",
    )
    s.add_argument(
        "--disagg", action="store_true",
        help="disaggregated prefill/decode serving (with --data-parallel "
        ">= 2, --kv-block-size/--kv-blocks and --prefix-cache): replicas "
        "get a role — prefill replicas admit fresh requests and stream "
        "each request's KV blocks to a decode replica after its first "
        "token, so long prefills never stall live streams' inter-token "
        "latency. The decode side resumes through the arena-gathered "
        "radix prefix (zero re-prefill FLOPs), token-identical to "
        "unified serving. Default split: 1 prefill replica, rest decode "
        "(override with --prefill-replicas or --roles)",
    )
    s.add_argument(
        "--prefill-replicas", type=int, default=0, dest="prefill_replicas",
        help="with --disagg: the first N replica groups take the prefill "
        "role, the rest decode (1 <= N <= replicas-1)",
    )
    s.add_argument(
        "--roles", default=None,
        help="with --disagg: explicit comma-separated per-replica roles, "
        "one of prefill/decode/unified per replica group, e.g. "
        "'prefill,decode,decode' (mutually exclusive with "
        "--prefill-replicas)",
    )
    s.add_argument(
        "--profile-json", default=None, dest="profile_json",
        help="with --disagg: a 'profile' command's profile.json (or its "
        "directory). The planner consumes the fitted prefill/decode "
        "latency models to route each request to the replica minimizing "
        "predicted TTFT (folding in radix-cache warmth) and to choose "
        "the prefill:decode ratio for the offered mix; without it the "
        "router falls back to health/warmth/load routing",
    )
    s.set_defaults(fn=cmd_serve)

    w = sub.add_parser(
        "worker",
        help="one multi-controller process (run one per host on a pod)",
    )
    w.add_argument("shards")
    w.add_argument("--coordinator", required=True, help="host:port of process 0")
    w.add_argument("--processes", type=int, required=True)
    w.add_argument("--process-id", type=int, required=True, dest="process_id")
    w.add_argument("--prompt", required=True)
    w.add_argument("--max-new", type=int, default=64, dest="max_new")
    w.add_argument("--stages", type=int)
    w.add_argument("--ranges")
    w.add_argument("--dtype", default="bf16")
    w.add_argument(
        "--local-devices", type=int, default=0, dest="local_devices",
        help="force N virtual CPU devices per process (simulation)",
    )
    w.add_argument(
        "--metrics-port", type=int, default=0, dest="metrics_port",
        help="expose /metrics on 127.0.0.1:(PORT + process-id) (0 = off)",
    )
    w.set_defaults(fn=cmd_worker)

    la = sub.add_parser(
        "launch",
        help="spawn N workers on this host over virtual CPU devices (a "
        "CPU simulation of a pod; on real hosts run `worker` per host)",
    )
    la.add_argument("shards")
    la.add_argument("--processes", type=int, default=2)
    la.add_argument("--prompt", required=True)
    la.add_argument("--max-new", type=int, default=64, dest="max_new")
    la.add_argument("--stages", type=int)
    la.add_argument("--ranges")
    la.add_argument("--dtype", default="bf16")
    la.add_argument(
        "--local-devices", type=int, default=0, dest="local_devices",
    )
    la.add_argument("--log-dir", default="results/launch", dest="log_dir")
    la.add_argument(
        "--timeout", type=float, default=900.0,
        help="kill all workers after this many seconds (0 = no limit)",
    )
    la.add_argument(
        "--metrics-port", type=int, default=0, dest="metrics_port",
        help="base port for per-worker /metrics endpoints: worker i binds "
        "PORT+i (0 = off)",
    )
    la.set_defaults(fn=cmd_launch)

    pr = sub.add_parser("profile", help="capability sweeps + artifacts")
    src = pr.add_mutually_exclusive_group(required=True)
    src.add_argument("--shards")
    src.add_argument(
        "--preset",
        help="config preset name (random weights), e.g. tiny_llama, llama32_3b",
    )
    pr.add_argument("--out", default="results/profiling")
    pr.add_argument("--dtype", default="bf16")
    pr.add_argument("--decode-tokens", type=int, default=64, dest="decode_tokens")
    pr.add_argument(
        "--hops", type=int, default=0,
        help="measure per-hop ppermute latency over an N-stage mesh",
    )
    pr.add_argument("--cold-start", action="store_true", dest="cold_start")
    pr.add_argument(
        "--hbm-gib", type=float, default=0.0, dest="hbm_gib",
        help="device memory to assume for max_layers_fit (auto-detected on "
        "TPU; omitted from the report when undeterminable)",
    )
    pr.add_argument(
        "--suggest-stages", type=int, default=0, dest="suggest_stages",
        help="emit a capability-weighted placement for N stages",
    )
    pr.set_defaults(fn=cmd_profile)

    tr = sub.add_parser(
        "trace-report",
        help="merge JSONL trace files, rebuild span trees, attribute "
        "latency per phase/tenant",
    )
    tr.add_argument(
        "files", nargs="+",
        help="trace files (globs ok): PATH, PATH.r<i>, PATH.router, "
        "PATH.ingress, PATH*.1 rollovers — any subset; spans join by "
        "trace_id",
    )
    tr.add_argument(
        "--top", type=int, default=5,
        help="how many slowest traces to list (default 5)",
    )
    tr.add_argument(
        "--trace", default=None,
        help="print one trace's full span tree instead of the summary",
    )
    tr.add_argument(
        "--json", action="store_true",
        help="machine-readable report (one JSON object)",
    )
    tr.set_defaults(fn=cmd_trace_report)

    sr = sub.add_parser(
        "step-report",
        help="render step-profiler captures (/profilez bundles, /debugz "
        "postmortems, :profile dumps): per-phase host-time attribution, "
        "occupancy timeline, worst device bubbles",
    )
    sr.add_argument(
        "files", nargs="+",
        help="capture JSON files (globs ok): /profilez?steps=N bundles, "
        "/debugz bundles (the recent_steps ring tails), or :profile "
        "output — any mix; records merge sorted by timestamp",
    )
    sr.add_argument(
        "--top", type=int, default=5,
        help="how many worst device-idle bubbles to list (default 5)",
    )
    sr.add_argument(
        "--json", action="store_true",
        help="machine-readable report (one JSON object)",
    )
    sr.set_defaults(fn=cmd_step_report)

    li = sub.add_parser(
        "lint",
        help="shardlint: repo-native static analysis (dispatch/shape-key "
        "completeness, donation safety, lock order, metrics/trace "
        "discipline); exits nonzero on new findings",
    )
    li.add_argument(
        "--rule", action="append", default=None,
        metavar="RULE",
        help="run only this rule (repeatable): dispatch-statics, "
        "donation-safety, lock-order, metrics-discipline, "
        "trace-discipline",
    )
    li.add_argument(
        "--json", action="store_true",
        help="machine-readable report (one JSON object)",
    )
    li.add_argument(
        "--baseline", default=None,
        help="baseline file of known finding fingerprints (default: "
        "llm_sharding_tpu/analysis/baseline.json — committed empty; the "
        "gate is strict)",
    )
    li.add_argument(
        "--write-baseline", action="store_true",
        help="regenerate the baseline from the current findings instead "
        "of failing on them (escape hatch — the intended state is an "
        "empty baseline)",
    )
    li.set_defaults(fn=cmd_lint)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=logging.INFO if args.verbose else logging.WARNING,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    if args.command in (
        "trace-report", "step-report", "lint", "launch", "convert",
    ):
        # No backend in this process. The first three are pure file
        # analysis (no jax import at all — they run on hosts with no
        # accelerator stack). ``launch`` is a parent that spawns the
        # processes which do the work: a chip belongs to one process, so a
        # parent that initialised a backend would hold every chip of the
        # host before its children start. ``convert`` is an offline file
        # transform and pins itself to the CPU.
        return args.fn(args)
    if args.command != "worker":
        # Compiled programs persist across daemon restarts and repeat runs
        # — utils/compile_cache.py says where, and why never on the CPU
        # backend. These commands initialise the backend in-process anyway,
        # so the jax.devices() probe costs nothing. ``worker`` must not
        # touch the backend before jax.distributed.initialize: it places
        # its cache right after joining (cmd_worker).
        import jax

        from .utils.compile_cache import enable_persistent_cache

        enable_persistent_cache(jax.devices()[0].platform)
    return args.fn(args)
