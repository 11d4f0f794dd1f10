"""Command-line interface — the TLC-shaped operator layer (SURVEY.md §1-L4).

Usage mirrors ``java tlc2.TLC``:

    python -m pulsar_tlaplus_tpu.cli check SPEC.tla [-config FILE.cfg]
        [-workers tpu | N] [-sharded N] [-invariant NAME ...]
        [-nodeadlock] [-cpu]

``check`` runs exhaustive BFS model checking of the named spec and prints
a TLC-style summary: distinct states, diameter, and a counterexample trace
on invariant violation or deadlock.  Modules with a compiled TPU model
(``models/registry.py`` COMPILED) run on the JAX engines; anything else —
or ``-interp`` — routes through the generic interpreter (host BFS,
engine/interp_check.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _jax_setup(args) -> None:
    """Backend choice and the one compile cache (utils/device.py),
    settled before anything compiles."""
    import jax

    from pulsar_tlaplus_tpu.utils.device import setup_compile_cache

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    setup_compile_cache()


def _positive_or_tpu(v: str):
    return v if v == "tpu" else int(v)


def _report(r, constants, wall: float, checkpoint=None) -> int:
    """TLC-style result report shared by the compiled and interpreter
    paths; returns the process exit code (0 ok, 1 violation/deadlock,
    3 truncated — a truncated search is NOT a verification result)."""
    from pulsar_tlaplus_tpu.utils.render import render_trace

    def _print_trace():
        if r.trace is None:
            # e.g. HBM exhaustion poisoned the trace logs: the verdict
            # stands but no counterexample can be reconstructed
            print("(trace unavailable: run was truncated before the "
                  "counterexample could be reconstructed)")
        else:
            print("The behavior up to this point is:")
            print(render_trace(r.trace, r.trace_actions, constants))

    if r.violation == "__EvalError__":
        print(
            "Error: evaluating the spec on this state is undefined "
            "(TLC would report an evaluation error here)."
        )
        _print_trace()
    elif r.violation and r.violation != "Deadlock":
        print(f"Error: Invariant {r.violation} is violated.")
        _print_trace()
    elif r.deadlock:
        print("Error: Deadlock reached.")
        _print_trace()
    print(
        f"{r.distinct_states} distinct states found, "
        f"search depth (diameter) {r.diameter}."
    )
    print(
        f"Finished in {wall:.1f}s "
        f"({r.states_per_sec:.0f} distinct states/sec)."
    )
    fp_p = getattr(r, "fp_collision_prob", 0.0)
    if fp_p:
        # TLC prints the analogous line after every fingerprinted run
        print(
            "The calculated (optimistic) probability of a fingerprint "
            f"collision at this state count is {fp_p:.3g}."
        )
    hbm_rec = getattr(r, "hbm_recovered", 0)
    if hbm_rec:
        print(
            f"Note: recovered from device-memory exhaustion {hbm_rec} "
            "time(s) by rebuilding from the checkpoint at degraded "
            "capacity."
        )
    if r.violation or r.deadlock:
        return 1
    if getattr(r, "truncated", False):
        reason = getattr(r, "stop_reason", None)
        if reason == "preempted":
            if checkpoint and os.path.exists(checkpoint):
                print(
                    "WARNING: search preempted (SIGTERM/SIGINT) — a "
                    "resumable checkpoint frame is on disk; continue "
                    "with -recover."
                )
            else:
                print(
                    "WARNING: search preempted (SIGTERM/SIGINT) before "
                    "any checkpoint frame could be written — the run "
                    "is NOT resumable."
                )
        else:
            print(
                "WARNING: search truncated by the state/time budget — the "
                "state space was NOT exhausted; absence of violations is "
                "inconclusive."
                + (f" (stop reason: {reason})" if reason else "")
            )
        return 3
    return 0


def _check_compiled_spec(args, module, spec_path, tlc_cfg, invariants):
    """Spec->kernel compiler path (SURVEY.md §2.2-E1): parse + bind,
    compile Init/Next/invariants to vmapped kernels, run the device BFS
    engine.  A spec that uses a construct outside the compilable subset
    is checked by the generic interpreter instead, and the check SAYS
    so ("spec->kernel compiler declined ...; falling back to the generic
    interpreter") and prints no compiled line: the benchmark's cell
    ``cli-compiled`` counts such a check as wrong, never as a slow one.

    Parse and bind run under ``ptt:cli.parse``, the ``CompiledSpec``
    constructor under ``ptt:cli.codegen`` (docs/observability.md "The
    compiled path")."""
    from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker
    from pulsar_tlaplus_tpu.frontend.codegen import CompiledSpec
    from pulsar_tlaplus_tpu.frontend.codegen_ir import CodegenError
    from pulsar_tlaplus_tpu.frontend.interp import Spec
    from pulsar_tlaplus_tpu.frontend.loader import bind_cfg
    from pulsar_tlaplus_tpu.frontend.parser import parse_file
    from pulsar_tlaplus_tpu.obs import spans

    t0 = time.time()
    t_parse = time.perf_counter()
    try:
        with spans.span("cli.parse"):
            ast = parse_file(spec_path)
            consts = bind_cfg(ast, tlc_cfg)
            interned = consts.pop("__string_interning__", None) or {}
            spec = Spec(ast, consts)
    except (ValueError, OSError) as e:
        sys.exit(f"tpu-tlc: {e}")
    parse_s = time.perf_counter() - t_parse
    try:
        with spans.span("cli.codegen"):
            cs = CompiledSpec(spec, invariants=invariants)
    except CodegenError as e:
        print(
            f"tpu-tlc: note: spec->kernel compiler declined ({e}); "
            "falling back to the generic interpreter"
        )
        return _check_interp(args, module, spec_path, tlc_cfg, invariants)
    cs.codegen_stats["codegen_parse_s"] = round(parse_s, 4)
    print(
        f"tpu-tlc: checking {module} @ {spec_path} via the spec->kernel "
        f"compiler (state width {cs.layout.total_bits} bits, {cs.A} "
        f"successor lanes; {_checking_what(args, invariants)})"
    )
    for cname, mapping in interned.items():
        pairs = ", ".join(f'"{s}" -> {i}' for s, i in mapping.items())
        print(f"tpu-tlc: note: {cname} strings interned as naturals: {pairs}")
    if args.simulate or args.sharded or args.liveness_property or (
        args.checkpoint or args.recover
    ):
        # every feature engine speaks the generic model protocol, so
        # the compiled spec routes through the same dispatch as the
        # hand-compiled registry models (round-2 judge item #4)
        return _dispatch_engines(args, cs, None, invariants, tlc_cfg, t0)
    try:
        with spans.span("cli.engine_init"):
            ck = DeviceChecker(
                cs,
                check_deadlock=not args.nodeadlock,
                **_explorer_tiers(args),
                max_states=args.maxstates,
                progress=True,
                metrics_path=args.metrics,
                fuse=args.fuse,
                fuse_group=args.fuse_group,
                hbm_budget=args.hbm_budget,
                spill_compress=(False if args.no_spill_compress else None),
                telemetry=args.telemetry,
                heartbeat_s=args.progress,
                xprof_dir=args.xprof,
                xprof_levels=args.xprof_window,
            )
        r = ck.run()
    except ValueError as e:
        sys.exit(f"tpu-tlc: {e}")
    with spans.span("cli.report"):
        rc = _report(r, None, time.time() - t0)
        _print_mode_lines(ck, r)
    if rc == 0 and tlc_cfg.properties:
        rc = _check_properties(args, cs, tlc_cfg.properties, rc)
    return rc


def _check_interp(args, module, spec_path, tlc_cfg, invariants):
    """Generic-interpreter check path: any spec in the supported subset."""
    from pulsar_tlaplus_tpu.engine.interp_check import InterpChecker
    from pulsar_tlaplus_tpu.frontend.interp import Spec
    from pulsar_tlaplus_tpu.frontend.loader import bind_cfg
    from pulsar_tlaplus_tpu.frontend.parser import parse_file

    if args.simulate or args.sharded or args.liveness_property:
        sys.exit(
            "tpu-tlc: -simulate/-sharded/-property need a compiled model "
            f"and the generic-interpreter path was selected for '{module}' "
            f"({'-interp forced' if args.interp else 'module not in the compiled registry'}); "
            "the interpreter path is exhaustive BFS only"
        )
    if (
        args.checkpoint or args.recover or args.metrics
        or args.telemetry or args.progress or args.xprof
    ):
        sys.exit(
            "tpu-tlc: -checkpoint/-recover/-metrics/-telemetry/"
            "-progress/-xprof are not supported on the generic-"
            "interpreter path yet"
        )
    if tlc_cfg.properties:
        print(
            "tpu-tlc: WARNING: cfg PROPERTIES "
            f"{list(tlc_cfg.properties)} are NOT checked on the "
            "generic-interpreter path (safety only)"
        )
    t0 = time.time()
    try:
        ast = parse_file(spec_path)
        consts = bind_cfg(ast, tlc_cfg)
        interned = consts.pop("__string_interning__", None) or {}
        spec = Spec(ast, consts)
        spec.check_assumes()
        print(
            f"tpu-tlc: checking {module} @ {spec_path} via the generic "
            f"interpreter (invariants: {list(invariants) or 'none'})"
        )
        for cname, mapping in interned.items():
            pairs = ", ".join(f'"{s}" -> {i}' for s, i in mapping.items())
            print(
                f"tpu-tlc: note: {cname} strings interned as naturals: {pairs}"
            )
        ck = InterpChecker(
            spec,
            invariants=invariants,
            check_deadlock=not args.nodeadlock,
            max_states=args.maxstates,
        )
        r = ck.run()
    except (ValueError, OSError) as e:
        # ParseError/LexError/EvalError subclass ValueError; OSError covers
        # a missing/unreadable spec file
        sys.exit(f"tpu-tlc: {e}")
    return _report(r, None, time.time() - t0)


def _explorer_tiers(args) -> dict:
    """Where every ``check`` starts its single-chip explorer, whatever
    the ``.cfg`` says and whichever question is asked (a safety check,
    or a temporal property through ``LivenessChecker``): the table is
    ``2 * visited_cap`` slots and grows lazily from there, so one
    binding meets the same table sizes, and the same programs' shapes,
    on both paths."""
    return dict(
        sub_batch=min(args.chunk, 4096),
        visited_cap=1 << 16,
        frontier_cap=1 << 14,
    )


def _checking_what(args, invariants) -> str:
    """The banner's last clause: what this check decides, and the
    device-memory budget it was given, if any."""
    budget = (
        f"; device-memory budget {args.hbm_budget}"
        if getattr(args, "hbm_budget", None) else ""
    )
    if getattr(args, "liveness_property", None):
        return (
            f"temporal property: {args.liveness_property} under "
            f"fairness {args.fairness}; no invariant checked{budget}"
        )
    return f"invariants: {list(invariants) or 'none'}{budget}"


def tiered_line(st: dict, distinct_states: int) -> str:
    """What a check under ``-hbm-budget`` spilled, from the engine's
    ``last_stats``: one line on stdout after the verdict, so that an
    untraced run can be held to its budget (docs/memory.md has the
    grammar)."""
    tc, lc, pc = st["spill_tier_ceilings"]
    return (
        f"Tiered store: budget {st['hbm_budget']} B (table <= {tc} "
        f"slots, rows <= {lc}, logs <= {pc}), hot tier peak "
        f"{st['spill_hot_keys_max']} keys "
        f"({100.0 * st['spill_hot_keys_max'] / max(distinct_states, 1):.1f}"
        f"% of {distinct_states}), {st['spill_evictions']} evictions of "
        f"{st['spill_keys_evicted']} keys, {st['spill_misses_resolved']} "
        f"cold lookups ({st['spill_miss_hits']} already visited), "
        f"{st['spill_rows_evicted']} rows spilled, budget overridden: "
        f"{'yes' if st['spill_budget_overridden'] else 'no'}."
    )


def recovered_line(st: dict) -> str:
    """What a check under ``-recover`` resumed from, from the engine's
    ``last_stats``: one line on stdout after the verdict, so that an
    untraced run can be held to its frame (docs/robustness.md has the
    grammar)."""
    return (
        f"Recovered from the checkpoint frame of level "
        f"{st['resume_level']} ({st['resume_states']} states): "
        f"{st['resume_levels_run']} levels expanded after it."
    )


def compiled_line(module: str, st: dict) -> str:
    """Which kernels a check of the spec->kernel compiler ran, from the
    engine's ``last_stats`` (the model's ``codegen_stats`` and the key
    kind): one line on stdout after the verdict, so that an untraced
    run can be held to its path and widths (docs/observability.md "The
    compiled path" has the grammar).  A check through a hand-written
    model, or one that fell back to the interpreter, prints none."""
    return (
        f"Compiled from the .tla: module {module}, state width "
        f"{st['codegen_state_bits']} bits in {st['codegen_state_words']} "
        f"words, {st['codegen_lanes']} successor lanes, "
        f"{st['codegen_initial_states']} initial states, keys "
        f"{'exact' if st['key_exact'] else 'hashed'}, code generation "
        f"{st['codegen_s']:.2f} s after {st['codegen_parse_s']:.2f} s "
        f"of parse and bind."
    )


def simulated_line(st: dict) -> str:
    """What a check in simulation mode walked, from the engine's
    ``result.stats``: one line on stdout after the verdict, so that an
    untraced run can be held to its swarm, its budget, its dump and
    its walk stream (docs/simulation.md has the grammar).  The digest
    is the engine's keys-digest of the final walker states: the same
    seed, walkers, depth and budget give the same one."""
    return (
        f"Simulated: {st['sim_walkers']} walkers of depth "
        f"{st['sim_depth']} in segments of {st['sim_segment_len']} "
        f"steps, {st['sim_rounds']} rounds, {st['sim_steps']} steps, "
        f"{st['sim_dump_behaviours']} behaviours dumped "
        f"({st['sim_dump_mismatches']} replay mismatches), final walker "
        f"states sha256 {st['sim_keys_digest']}."
    )


def _print_mode_lines(ck, r) -> None:
    """After the verdict, one line for each mode a check ran in that
    an untraced run is held to: compiled, tiered, recovered."""
    st = getattr(ck, "last_stats", {})
    if "codegen_s" in st:
        print(compiled_line(ck.model.spec.module.name, st))
    if "spill_tier_ceilings" in st:
        print(tiered_line(st, r.distinct_states))
    if "resume_levels_run" in st:
        print(recovered_line(st))


def _print_graph_summary(graph) -> None:
    """The behaviour graph a liveness verdict was computed on, so that
    an untraced run can be held to a reference: one line for the whole
    graph and one per BFS level."""
    if not graph:
        return
    # no fairness assumed: the verdict needed no edge and none was swept
    swept = graph["edges"] is not None
    print(
        f"Behaviour graph: {graph['states']} states in "
        f"{graph['levels']} levels, "
        f"{graph['edges'] if swept else 'n/a'} <Next>_vars edges, "
        f"{graph['goal_states']} goal states, "
        f"{graph['dead_ends'] if swept else 'n/a'} dead ends."
    )
    by = graph.get("by_level")
    if not by:
        return
    unswept = ["n/a"] * len(by["size"])
    for i, row in enumerate(zip(
        by["size"], by.get("edges", unswept), by["goal"],
        by.get("dead_ends", unswept),
    )):
        print(
            "  graph level {}: {} states, {} edges, {} goal, "
            "{} dead ends".format(i + 1, *row)
        )


def _report_liveness(prop, args, lres) -> int:
    """Liveness verdict report + exit code (0 holds, 1 violated, 3
    preempted/truncated — an interrupted run carries NO verdict)."""
    if lres.truncated:
        if lres.stop_reason == "preempted":
            if args.checkpoint and os.path.exists(args.checkpoint):
                print(
                    f"Temporal property {prop}: run preempted "
                    "(SIGTERM/SIGINT) — no verdict.  A resumable "
                    "frame is on disk; continue with -recover."
                )
            else:
                print(
                    f"Temporal property {prop}: run preempted "
                    "(SIGTERM/SIGINT) before any frame could be "
                    "written — no verdict, and the run is NOT "
                    "resumable."
                )
        else:
            print(
                f"Temporal property {prop}: run truncated "
                f"({lres.stop_reason or 'unknown'}) — no verdict."
            )
        return 3
    verdict = "satisfied" if lres.holds else "VIOLATED"
    print(
        f"Temporal property {prop} (fairness={args.fairness}): "
        f"{verdict} — {lres.reason}"
    )
    print(f"{lres.distinct_states} distinct states examined.")
    _print_graph_summary(lres.graph)
    return 0 if lres.holds else 1


def _report_simulation(sres, constants, checkpoint=None) -> int:
    """TLC-``-simulate``-shaped report + exit code (0 clean, 1
    violation, 3 interrupted — an interrupted walk stream carries no
    conclusion and resumes with -recover), then the simulated line."""
    rc = _report_simulation_verdict(sres, constants, checkpoint)
    print(simulated_line(sres.stats))
    return rc


def _report_simulation_verdict(sres, constants, checkpoint) -> int:
    from pulsar_tlaplus_tpu.utils.render import render_trace

    if sres.violation:
        print(f"Error: Invariant {sres.violation} is violated.")
        print("The behavior up to this point is:")
        print(render_trace(sres.trace, sres.trace_actions, constants))
        if sres.verified is False:
            print(
                "WARNING: the replayed behavior FAILED independent "
                "re-verification — report this as an engine bug."
            )
    print(
        f"Simulation: {sres.n_walkers} walkers of depth {sres.depth} "
        f"({sres.states_visited} states visited, {sres.steps} steps, "
        f"{sres.walks} completed walks)."
    )
    # the wall holds each program's first dispatch (trace, lowering,
    # load): the rate of the dispatches after those stands beside it
    steady = (
        f", {sres.steady_steps_per_sec:,.0f} steps/sec after each "
        "program's first dispatch"
        if sres.steady_steps_per_sec
        else ""
    )
    print(
        f"Finished in {sres.wall_s:.1f}s ({sres.steps_per_sec:,.0f} "
        f"steps/sec{steady}, {sres.walks_per_sec:,.1f} walks/sec)"
        + (
            f"; sampled duplicate ratio ~{sres.dup_ratio_est:.1%}."
            if sres.dup_ratio_est is not None
            else "."
        )
    )
    if sres.dump_files:
        print(
            f"{len(sres.dump_files)} behaviours of the last round "
            f"written to {sres.dump_files[0]} ... "
            f"{os.path.basename(sres.dump_files[-1])}."
        )
    if sres.violation:
        return 1
    if sres.truncated:
        if sres.stop_reason == "preempted" and checkpoint and (
            os.path.exists(checkpoint)
        ):
            print(
                "WARNING: simulation preempted (SIGTERM/SIGINT) — a "
                "resumable frame is on disk; continue the identical "
                "walk stream with -recover."
            )
        else:
            print(
                "WARNING: simulation interrupted "
                f"({sres.stop_reason or 'unknown'}) — the walk "
                "stream did not reach its budget."
            )
        return 3
    print(
        "No violation found within the simulation budget "
        f"(stop reason: {sres.stop_reason}); simulation is NOT "
        "exhaustive — absence of violations is inconclusive."
    )
    return 0


def _run_simulation(args, make_sim, constants) -> int:
    """Build and run a ``StreamingSimulator`` for ``check -simulate``
    and ``simulate`` alike and report it; a dump asked for with a
    budget that ends off a round boundary is exit 2."""
    from pulsar_tlaplus_tpu.sim.engine import DumpBudgetError

    try:
        sres = make_sim().run(resume=args.recover)
    except DumpBudgetError as e:
        print(f"tpu-tlc: -sim-dump: {e}", file=sys.stderr)
        return 2
    except FileNotFoundError:
        sys.exit(
            "tpu-tlc: -recover needs an existing -checkpoint file "
            f"(got: {args.checkpoint})"
        )
    except (ValueError, RuntimeError) as e:
        sys.exit(f"tpu-tlc: {e}")
    return _report_simulation(sres, constants, args.checkpoint)


def _check_properties(args, model, properties, rc):
    """Check cfg PROPERTIES after a clean safety pass (TLC checks
    temporal properties from the same run); shared by the registry and
    spec->kernel compiler paths."""
    from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker

    lck = None
    for prop in properties:
        goals = getattr(model, "liveness_goals", {})
        if prop not in goals:
            # e.g. a temporal formula outside the <>(predicate)
            # fragment on the compiled path: the safety verdict stands,
            # matching the old warn-only behavior
            print(
                f"tpu-tlc: WARNING: cfg PROPERTIES entry {prop} is not "
                "checkable here (only <>(predicate) properties are "
                "supported); safety verdict unaffected"
            )
            continue
        try:
            if lck is None:
                lck = LivenessChecker(
                    model,
                    goal=prop,
                    fairness=args.fairness,
                    frontier_chunk=args.chunk,
                    visited_cap=_explorer_tiers(args)["visited_cap"],
                    max_states=args.maxstates,
                    # the safety phase completed cleanly, so its frame
                    # at this path is obsolete — the liveness phase
                    # takes over the checkpoint file (TLC-style: one
                    # states location per invocation)
                    checkpoint_path=args.checkpoint,
                    sweep_group=args.sweep_group,
                    hbm_budget=args.hbm_budget,
                    spill_compress=(False if args.no_spill_compress else None),
                    telemetry=args.telemetry,
                    heartbeat_s=args.progress,
                    progress=True,
                )
                lres = lck.run()
            else:
                # later properties reuse the same explored state
                # space and edge list (one BFS for all PROPERTIES)
                lres = lck.run_goal(prop)
        except (ValueError, RuntimeError) as e:
            sys.exit(f"tpu-tlc: {e}")
        if lres.truncated:
            # preemption/truncation carries NO verdict; stop checking
            # further properties (the operator asked the run to end).
            # _report_liveness prints the resume guidance (-recover)
            return _report_liveness(prop, args, lres)
        verdict = "satisfied" if lres.holds else "VIOLATED"
        print(
            f"Temporal property {prop} (fairness={args.fairness}): "
            f"{verdict} — {lres.reason}"
        )
        if not lres.holds:
            rc = 1
    return rc


def _dispatch_engines(args, model, constants, invariants, tlc_cfg, t0):
    """Engine selection shared by the registry and spec->kernel compiler
    paths: liveness property, simulation, sharded (device or host), or
    the single-device checker — all via the generic model protocol."""
    from pulsar_tlaplus_tpu.obs import spans
    from pulsar_tlaplus_tpu.utils.render import render_trace

    if args.xprof and (
        args.liveness_property or args.simulate or args.sharded
        or args.engine != "device"
    ):
        # never let a user wait out a long run believing a profile was
        # collected: level-windowed tracing exists only on the
        # single-chip device engine (-profile traces any whole check)
        print(
            "tpu-tlc: note: -xprof is only supported on the "
            "single-chip device engine; no trace will be captured "
            "(use -profile DIR to trace the whole check)",
            file=sys.stderr,
        )
    if args.liveness_property:
        from pulsar_tlaplus_tpu.engine.liveness import LivenessChecker

        try:
            lck = LivenessChecker(
                model,
                goal=args.liveness_property,
                fairness=args.fairness,
                frontier_chunk=args.chunk,
                visited_cap=_explorer_tiers(args)["visited_cap"],
                max_states=args.maxstates,
                checkpoint_path=args.checkpoint,
                sweep_group=args.sweep_group,
                hbm_budget=args.hbm_budget,
                spill_compress=(False if args.no_spill_compress else None),
                telemetry=args.telemetry,
                heartbeat_s=args.progress,
                progress=True,
            )
            lres = lck.run(resume=args.recover)
        except FileNotFoundError:
            sys.exit(
                "tpu-tlc: -recover needs an existing -checkpoint file "
                f"(got: {args.checkpoint})"
            )
        except (ValueError, RuntimeError) as e:
            sys.exit(f"tpu-tlc: {e}")
        return _report_liveness(args.liveness_property, args, lres)
    if args.simulate:
        # the streaming swarm engine (sim/, round 18): full telemetry,
        # heartbeat and checkpoint/resume — the legacy one-round semantics are the default budget
        from pulsar_tlaplus_tpu.sim.engine import StreamingSimulator

        return _run_simulation(
            args,
            lambda: StreamingSimulator(
                model,
                invariants=invariants,
                n_walkers=args.simulate,
                depth=args.depth,
                segment_len=args.segment,
                seed=args.sim_seed,
                max_steps=args.sim_steps,
                checkpoint_path=args.checkpoint,
                telemetry=args.telemetry,
                heartbeat_s=args.progress,
                progress=True,
                dump_path=args.sim_dump,
                dump_num=args.sim_dump_num,
            ),
            constants,
        )
    if args.sharded and (
        args.sharded_engine == "device"
        and args.sharded_dedup == "sort"
    ):
        from pulsar_tlaplus_tpu.engine.sharded_device import (
            ShardedDeviceChecker,
        )

        if args.slices > 1 and args.sharded % args.slices:
            sys.exit("tpu-tlc: -sharded must be divisible by -slices")
        with spans.span("cli.engine_init"):
            ck = ShardedDeviceChecker(
                model,
                n_devices=args.sharded,
                invariants=invariants,
                check_deadlock=not args.nodeadlock,
                sub_batch=args.chunk,
                max_states=args.maxstates,
                metrics_path=args.metrics,
                progress=True,
                checkpoint_path=args.checkpoint,
                n_slices=args.slices,
                telemetry=args.telemetry,
                heartbeat_s=args.progress,
            )
    elif args.sharded:
        if args.sharded_engine == "device":
            print(
                "tpu-tlc: note: -sharded-dedup hash needs the "
                "host-staged sharded driver; using -sharded-engine host"
            )
        from pulsar_tlaplus_tpu.engine.sharded import ShardedChecker

        mesh = None
        if args.slices > 1:
            from pulsar_tlaplus_tpu.parallel.mesh import make_mesh2d

            if args.sharded % args.slices:
                sys.exit("tpu-tlc: -sharded must be divisible by -slices")
            mesh = make_mesh2d(args.slices, args.sharded // args.slices)
        ck = ShardedChecker(
            model,
            n_devices=args.sharded,
            invariants=invariants,
            check_deadlock=not args.nodeadlock,
            frontier_chunk=args.chunk,
            max_states=args.maxstates,
            mesh=mesh,
            dedup_mode=args.sharded_dedup,
            metrics_path=args.metrics,
            checkpoint_path=args.checkpoint,
            telemetry=args.telemetry,
            heartbeat_s=args.progress,
        )
    elif args.engine == "device":
        # the flagship single-chip engine (the one every BENCH runs) —
        # with full -checkpoint/-recover survivability (round 7; TLC's
        # states/ directory contract on the device-resident path)
        from pulsar_tlaplus_tpu.engine.device_bfs import DeviceChecker

        with spans.span("cli.engine_init"):
            ck = DeviceChecker(
                model,
                invariants=invariants,
                check_deadlock=not args.nodeadlock,
                **_explorer_tiers(args),
                max_states=args.maxstates,
                progress=True,
                metrics_path=args.metrics,
                fuse=args.fuse,
                fuse_group=args.fuse_group,
                hbm_budget=args.hbm_budget,
                spill_compress=(
                    False if args.no_spill_compress else None
                ),
                checkpoint_path=args.checkpoint,
                telemetry=args.telemetry,
                heartbeat_s=args.progress,
                xprof_dir=args.xprof,
                xprof_levels=args.xprof_window,
            )
    else:
        from pulsar_tlaplus_tpu.engine.bfs import Checker

        ck = Checker(
            model,
            invariants=invariants,
            check_deadlock=not args.nodeadlock,
            frontier_chunk=args.chunk,
            max_states=args.maxstates,
            progress=True,
            metrics_path=args.metrics,
            checkpoint_path=args.checkpoint,
            telemetry=args.telemetry,
            heartbeat_s=args.progress,
        )
    if args.recover and (
        not args.checkpoint or not os.path.exists(args.checkpoint)
    ):
        sys.exit(
            f"tpu-tlc: -recover needs an existing -checkpoint file "
            f"(got: {args.checkpoint})"
        )
    try:
        r = ck.run(resume=args.recover)
    except (ValueError, RuntimeError) as e:
        msg = str(e)
        if (
            args.recover
            and not args.sharded
            and args.engine == "device"
            and "written by a different" in msg
        ):
            # the r7 engine-default switch: frames from the pre-r7
            # default (the host engine) carry a different signature —
            # point the operator at the engine that wrote them
            msg += (
                " (checkpoints written by the pre-r7 default host "
                "engine resume with -engine host)"
            )
        sys.exit(f"tpu-tlc: {msg}")
    with spans.span("cli.report"):
        rc = _report(
            r, constants, time.time() - t0, checkpoint=args.checkpoint
        )
        _print_mode_lines(ck, r)
    # cfg PROPERTIES are honored automatically after a clean safety pass
    # (TLC checks temporal properties from the same run); the sharded
    # drivers do not keep the state log the liveness engine needs
    if rc == 0 and not args.sharded and tlc_cfg.properties:
        rc = _check_properties(args, model, tlc_cfg.properties, rc)
    return rc


# ---------------------------------------------- checking-as-a-service

DEFAULT_STATE_DIR = os.path.expanduser("~/.ptt_serve")


def _socket_of(args) -> str:
    """Client socket resolution: explicit --socket wins; otherwise the
    daemon's well-known location inside --state-dir."""
    if getattr(args, "socket", None):
        return args.socket
    return os.path.join(
        os.path.abspath(args.state_dir), "serve.sock"
    )


def _service_client(args):
    from pulsar_tlaplus_tpu.service.client import ServiceClient

    return ServiceClient(
        _socket_of(args),
        timeout=args.timeout,
        token=getattr(args, "token", None),
        retries=getattr(args, "retries", 4),
    )


def _client_die(msg: str):
    """Transport/daemon failure: exit 2 (no verification verdict).
    Never 1 — the exit-code contract reserves 1 for violation/
    deadlock, and a CI pipeline must be able to tell "the daemon was
    down" from "the spec is broken"."""
    print(f"tpu-tlc: {msg}", file=sys.stderr)
    sys.exit(2)


def _client_fail(op: str, e) -> None:
    """Map a client-side failure to the exit-code contract on EVERY
    subcommand: 4 = auth rejected, 5 = over quota / load shed, 2 =
    transport/daemon failure — so `status` with an expired token
    reads "fix my token", not "the daemon is down"."""
    from pulsar_tlaplus_tpu.service.client import (
        AdmissionRejected,
        AuthError,
        BackendUnavailable,
    )

    if isinstance(e, AuthError):
        print(f"tpu-tlc: {op} rejected (auth): {e}", file=sys.stderr)
        sys.exit(4)
    if isinstance(e, AdmissionRejected):
        print(
            f"tpu-tlc: {op} rejected ({e.code}): {e}", file=sys.stderr
        )
        sys.exit(5)
    if isinstance(e, BackendUnavailable):
        # the fleet had no healthy backend even after the retry
        # budget: transport-class (exit 2), NEVER a spec verdict
        _client_die(f"{op}: fleet has no healthy backend: {e}")
    _client_die(f"{op} failed: {e}")


def _print_job_line(j: dict) -> None:
    extra = ""
    if j.get("state") == "done" and (
        "status" in j or "distinct_states" in j or "steps" in j
    ):
        if j.get("mode") == "simulate":
            extra = (
                f"  {j.get('status', '?')} "
                f"{j.get('steps', '?')} sim steps"
            )
        else:
            extra = (
                f"  {j.get('status', '?')} "
                f"{j.get('distinct_states', '?')} states"
            )
    elif j.get("error"):
        extra = f"  {j['error'][:80]}"
    warm = ""
    if j.get("warm_mode"):
        # the reuse decision (docs/incremental.md): continue / reseed
        # with its match, or cold with the typed fallback reason
        warm = f" warm={j['warm_mode']}:{j.get('warm_reason')}"
    # a fleet listing row names its owning backend (and may omit the
    # slice counters, which live on the backend, not the dispatcher)
    at = f" @{j['backend']}" if j.get("backend") else ""
    print(
        f"{j['job_id']}  {j.get('spec') or '?':<16} "
        f"{j.get('state') or '?':<10} "
        f"slices={j.get('slices', 0)} suspends={j.get('suspends', 0)}"
        f"{warm}{extra}{at}"
    )


def _service_exit(state: str, result, error) -> int:
    """Exit-code contract mirroring ``check``: 0 clean, 1 violation/
    deadlock, 2 failed/cancelled, 3 truncated (no verification
    verdict)."""
    if state == "done" and result:
        status = result.get("status")
        if status == "ok":
            return 0
        if status in ("violation", "deadlock"):
            return 1
        return 3  # truncated: NOT a verification result
    return 2


def _report_job_result(job_id: str, state: str, result, error) -> int:
    if state == "done" and result:
        status = result.get("status")
        if status in ("violation", "deadlock"):
            name = result.get("violation") or "Deadlock"
            print(f"Error: job {job_id}: {name}.")
            if result.get("trace"):
                print("The behavior up to this point is:")
                for i, (s, a) in enumerate(
                    zip(
                        result["trace"],
                        ["<init>"] + (result.get("trace_actions") or []),
                    )
                ):
                    print(f"  {i + 1}: [{a}] {s}")
        if result.get("mode") == "simulate":
            print(
                f"Simulation: {result.get('steps')} steps, "
                f"{result.get('states_visited')} states visited, "
                f"{result.get('walks')} completed walks."
            )
        else:
            print(
                f"{result.get('distinct_states')} distinct states "
                f"found, search depth (diameter) "
                f"{result.get('diameter')}."
            )
        print(
            f"Job {job_id} finished in {result.get('wall_s')}s over "
            f"{result.get('slices')} slice(s) "
            f"({result.get('suspends')} suspension(s))."
        )
        if status == "truncated":
            print(
                "WARNING: search truncated "
                f"(stop reason: {result.get('stop_reason')}) — "
                "absence of violations is inconclusive."
            )
    elif error:
        print(f"Job {job_id} FAILED: {error}")
    else:
        print(f"Job {job_id}: {state}")
    return _service_exit(state, result, error)


def _cmd_serve(args) -> int:
    _jax_setup(args)
    from pulsar_tlaplus_tpu.service.scheduler import ServiceConfig
    from pulsar_tlaplus_tpu.service.server import ServiceDaemon

    def log(msg: str) -> None:
        print(f"tpu-tlc serve: {msg}", file=sys.stderr, flush=True)

    config = ServiceConfig(
        state_dir=os.path.abspath(args.state_dir),
        socket_path=args.socket or "",
        devices=args.devices,
        slice_s=args.slice,
        max_states=args.maxstates,
        checkpoint_every=args.checkpoint_every,
        keep_terminal=args.keep_terminal,
        sub_batch=min(args.chunk, 4096),
        specs=tuple(args.spec or ()),
        prewarm_tiers=not args.no_tiers,
        tcp=args.tcp or "",
        tokens_path=args.tokens or "",
        queue_cap=args.queue_cap,
        tenant_max_queued=args.tenant_max_queued,
        tenant_max_running=args.tenant_max_running,
        tenant_max_states=args.tenant_max_states,
        **(
            {"warm_max_bytes": args.warm_max_bytes}
            if args.warm_max_bytes is not None
            else {}
        ),
    )
    try:
        daemon = ServiceDaemon(config, recover=args.recover, log=log)
    except (RuntimeError, ValueError) as e:  # lock held / bad tokens
        sys.exit(f"tpu-tlc: {e}")
    if not args.no_prewarm:
        daemon.prewarm()
    try:
        daemon.start()
    except OSError as e:  # TCP bind failure (port in use, EACCES)
        daemon.shutdown()
        sys.exit(f"tpu-tlc: cannot listen: {e}")
    daemon.install_signal_handlers()
    # the ready line goes to STDOUT so wrappers/tests can block on it
    print(f"serving on {config.socket_path}", flush=True)
    if daemon.tcp_port is not None:
        print(f"serving on tcp port {daemon.tcp_port}", flush=True)
    daemon.serve_forever(drain=args.drain)
    return 0


def _cmd_dispatch(args) -> int:
    from pulsar_tlaplus_tpu.fleet.dispatcher import (
        FleetConfig,
        FleetDispatcher,
    )

    def log(msg: str) -> None:
        print(f"tpu-tlc dispatch: {msg}", file=sys.stderr, flush=True)

    config = FleetConfig(
        state_dir=os.path.abspath(args.state_dir),
        backends=tuple(args.backend or ()),
        socket_path=args.socket or "",
        tcp=args.tcp or "",
        tokens_path=args.tokens or "",
        health_interval_s=args.health_interval,
        fail_after=args.fail_after,
        backend_timeout_s=args.backend_timeout,
        replicate=not args.no_replicate,
        recover=args.recover,
        readmit_after=args.readmit_after,
        hold_max=args.hold_max,
        hold_s=args.hold_s,
    )
    try:
        disp = FleetDispatcher(config, log=log)
    except (RuntimeError, ValueError) as e:  # lock held / bad tokens
        sys.exit(f"tpu-tlc: {e}")
    try:
        disp.start()
    except OSError as e:
        disp.shutdown()
        sys.exit(f"tpu-tlc: cannot listen: {e}")
    disp.install_signal_handlers()
    # the ready line goes to STDOUT so wrappers/tests can block on it
    print(f"dispatching on {config.socket_path}", flush=True)
    if disp.tcp_port is not None:
        print(f"dispatching on tcp port {disp.tcp_port}", flush=True)
    disp.serve_forever()
    return 0


def _cmd_submit(args) -> int:
    from pulsar_tlaplus_tpu.service.client import ServiceError

    sim = None
    if args.mode == "simulate":
        sim = {
            k: v
            for k, v in (
                ("n_walkers", args.walkers),
                ("depth", args.depth),
                ("segment_len", args.segment),
                ("seed", args.sim_seed),
                ("max_steps", args.sim_steps),
            )
            if v is not None
        }
    cl = _service_client(args)
    try:
        reply = cl.submit(
            args.spec,
            os.path.abspath(args.config),
            invariants=args.invariant,
            max_states=args.maxstates,
            time_budget_s=args.time_budget,
            priority=args.priority,
            deadline_s=args.deadline_s,
            submit_id=args.submit_id,
            mode=args.mode,
            sim=sim,
            warm=not args.no_warm,
            full=True,
        )
        jid = reply["job_id"]
    except (ServiceError, OSError) as e:
        # distinct exit codes for rejected-at-the-door (docs/
        # service.md "Admission"): 4 = bad/missing token, 5 = over
        # quota / load shed — a CI lane tells "fix my token" from
        # "back off" from "the daemon is down" (2) without parsing
        _client_fail("submit", e)
    print(jid)
    if reply.get("warm_mode"):
        # the reuse plan, up front (docs/incremental.md): continue /
        # reseed with its match, or cold with the typed reason
        print(
            f"warm plan: {reply['warm_mode']} "
            f"({reply.get('warm_reason')})",
            file=sys.stderr,
        )
    if args.watch:
        return _watch_stream(cl, jid, args.timeout)
    if args.wait:
        try:
            r = cl.wait(jid, timeout=args.timeout)
        except TimeoutError as e:
            _client_die(str(e))
        return _report_job_result(
            jid, r.get("state"), r.get("result"), r.get("error")
        )
    return 0


def _cmd_status(args) -> int:
    from pulsar_tlaplus_tpu.service.client import ServiceError

    cl = _service_client(args)
    try:
        if args.job_id:
            _print_job_line(cl.status(args.job_id))
        else:
            jobs = cl.status()
            if not jobs:
                print("(no jobs)")
            for j in jobs:
                _print_job_line(j)
    except (ServiceError, OSError) as e:
        _client_fail("status", e)
    return 0


def _watch_stream(cl, job_id: str, timeout: float) -> int:
    """Stream a job's relayed telemetry to stdout; returns the job's
    exit code from the terminating ``done`` message."""
    from pulsar_tlaplus_tpu.service.client import ServiceError

    try:
        for msg in cl.watch(job_id, timeout_s=timeout):
            if "event" in msg:
                e = msg["event"]
                kind = e.get("event", "?")
                if kind == "level":
                    print(
                        f"[{e.get('run_id', '?')[:6]}] level "
                        f"{e.get('level')}: {e.get('distinct_states')} "
                        f"distinct, frontier {e.get('frontier')}, "
                        f"{e.get('states_per_sec')} st/s",
                        flush=True,
                    )
                elif kind in ("run_header", "result", "progress",
                              "ckpt_frame"):
                    print(
                        f"[{e.get('run_id', '?')[:6]}] {kind} "
                        + " ".join(
                            f"{k}={e[k]}"
                            for k in (
                                "resume", "distinct_states", "wall_s",
                                "frame_seq", "states_per_sec",
                            )
                            if k in e
                        ),
                        flush=True,
                    )
            elif "done" in msg:
                d = msg["done"]
                return _report_job_result(
                    job_id, d.get("state"), d.get("result"),
                    d.get("error"),
                )
            elif "error" in msg or not msg.get("ok", True):
                _client_die(f"watch: {msg.get('error')}")
    except (ServiceError, OSError) as e:
        _client_fail("watch", e)
    return 2  # stream ended without a done record


def _cmd_watch(args) -> int:
    return _watch_stream(_service_client(args), args.job_id, args.timeout)


def _cmd_cancel(args) -> int:
    from pulsar_tlaplus_tpu.service.client import ServiceError

    cl = _service_client(args)
    try:
        state = cl.cancel(args.job_id)
    except (ServiceError, OSError) as e:
        _client_fail("cancel", e)
    print(f"{args.job_id}: {state}")
    return 0


def _cmd_trace(args) -> int:
    """Telemetry stream(s) -> Perfetto-loadable Chrome trace JSON."""
    from pulsar_tlaplus_tpu.obs import report, trace

    # label streams by basename stem; the documented
    # `trace jobs/*/events.jsonl` shape would name every process
    # "events", so collisions pull in the parent directory (the job id)
    stems = [
        os.path.splitext(os.path.basename(p))[0] for p in args.stream
    ]

    def label(i: int) -> str:
        if stems.count(stems[i]) == 1:
            return stems[i]
        parent = os.path.basename(
            os.path.dirname(os.path.abspath(args.stream[i]))
        )
        return f"{parent}/{stems[i]}" if parent else stems[i]

    streams = []
    for i, p in enumerate(args.stream):
        try:
            events, errors = report.load_events(p)
        except OSError as e:
            print(f"tpu-tlc: {e}", file=sys.stderr)
            return 2
        for e in errors:
            print(f"tpu-tlc: {p}: WARNING: {e}", file=sys.stderr)
        if not events:
            print(f"tpu-tlc: {p}: no telemetry events", file=sys.stderr)
            return 2
        streams.append((label(i), events))
    tr = trace.write_trace(streams, args.output)
    n = sum(1 for e in tr["traceEvents"] if e.get("ph") != "M")
    print(
        f"wrote {args.output}: {n} event(s) from {len(streams)} "
        "stream(s) — open in https://ui.perfetto.dev"
    )
    return 0


def _cmd_metrics(args) -> int:
    """Prometheus text metrics: scrape the daemon, or derive the same
    families from a telemetry stream tail (--stream)."""
    from pulsar_tlaplus_tpu.service.client import ServiceError

    if args.stream:
        from pulsar_tlaplus_tpu.obs import metrics as metrics_mod
        from pulsar_tlaplus_tpu.obs import report

        try:
            events, errors = report.load_events(args.stream)
        except OSError as e:
            print(f"tpu-tlc: {e}", file=sys.stderr)
            return 2
        for e in errors:
            print(
                f"tpu-tlc: {args.stream}: WARNING: {e}", file=sys.stderr
            )
        sys.stdout.write(metrics_mod.render_stream_metrics(events))
        return 0
    cl = _service_client(args)
    try:
        sys.stdout.write(
            cl.metrics(aggregate=bool(getattr(args, "aggregate", False)))
        )
    except (ServiceError, OSError) as e:
        _client_fail("metrics", e)
    return 0


def _cmd_top(args) -> int:
    """Live ANSI dashboard: poll the daemon (default) or tail a
    telemetry stream (--stream).  --once renders a single frame (no
    clear codes) and exits — the scriptable/test mode."""
    from pulsar_tlaplus_tpu.obs import top as top_mod
    from pulsar_tlaplus_tpu.service.client import ServiceError

    if args.stream:
        model = top_mod.TopModel(", ".join(args.stream))

        def frame():
            return top_mod.tail_stream_frame(args.stream, model)
    elif getattr(args, "dispatch", False):
        # fleet flight deck (r22): one dispatcher ping + one
        # aggregate scrape per tick
        cl = _service_client(args)
        fleet_model = top_mod.FleetTopModel(_socket_of(args))

        def frame():
            return top_mod.poll_dispatch_frame(cl, fleet_model)
    else:
        cl = _service_client(args)
        model = top_mod.TopModel(_socket_of(args))

        def frame():
            return top_mod.poll_daemon_frame(cl, model)

    try:
        while True:
            try:
                text = frame()
            except (ServiceError, OSError) as e:
                _client_fail("top", e)
            if args.once:
                print(text)
                return 0
            sys.stdout.write(top_mod.CLEAR + text + "\n")
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        print()
        return 0


def _cmd_ledger(args) -> int:
    """The cross-run regression ledger (obs/ledger.py,
    docs/observability.md "Attribution"): ingest BENCH artifacts and
    telemetry streams into an append-only JSONL ledger, render
    trajectory tables and per-run deltas, and gate regressions."""
    from pulsar_tlaplus_tpu.obs import ledger

    path = args.ledger

    def _rec_of(ref: str, recs):
        # a REF that names an existing file ingests on the fly, so
        # `ledger compare bench_a.json bench_b.json` works with no
        # ledger file at all
        if os.path.exists(ref):
            return ledger.record_from_file(ref)
        return ledger.resolve(recs, ref)

    if args.ledger_cmd == "add":
        recs = []
        for p in args.files:
            try:
                recs.append(ledger.record_from_file(p))
            except (OSError, ValueError, json.JSONDecodeError) as e:
                print(f"tpu-tlc: {p}: {e}", file=sys.stderr)
                return 2
        added = ledger.append(path, recs)
        print(
            f"ingested {added} new record(s) of {len(recs)} into "
            f"{path} ({len(ledger.load(path))} total)"
        )
        return 0
    recs = ledger.load(path)
    if args.ledger_cmd == "list":
        print(ledger.render_list(recs, key=args.key))
        return 0
    try:
        if args.ledger_cmd == "show":
            print(ledger.render_show(_rec_of(args.ref, recs)))
            return 0
        if args.ledger_cmd == "compare":
            a = _rec_of(args.ref_a, recs)
            b = _rec_of(args.ref_b, recs)
            print(ledger.render_compare(a, b))
            return 0
        if args.ledger_cmd == "gate":
            if args.current:
                cur = _rec_of(args.current, recs)
            elif recs:
                cur = recs[-1]
            else:
                print("tpu-tlc: empty ledger, nothing to gate",
                      file=sys.stderr)
                return 2
            if args.baseline:
                base = _rec_of(args.baseline, recs)
            else:
                # newest record PRECEDING the current one with the
                # SAME config key — gating an older record must never
                # pick a newer run as its baseline (that would invert
                # the comparison)
                cut = next(
                    (
                        i for i, r in enumerate(recs)
                        if r.get("digest") == cur.get("digest")
                    ),
                    len(recs),
                )
                base = next(
                    (
                        r for r in reversed(recs[:cut])
                        if r.get("key") == cur.get("key")
                        # tuned-vs-default context (r15): "same"
                        # gates tuned against tuned and default
                        # against default; "none" gates a tuned run
                        # against the hand-default baseline — the
                        # "tuning never regresses" check
                        and ledger.baseline_matches_profile(
                            r, args.profile, cur
                        )
                        # warm-start context (r19): a warm-continue
                        # partial never baselines a cold run (and
                        # vice versa) — its counters cover only the
                        # resumed suffix of the search
                        and ledger.baseline_matches_warm(r, cur)
                    ),
                    None,
                )
                if base is None:
                    print(
                        "tpu-tlc: no baseline with a matching config "
                        f"key, profile context ({args.profile!r}), "
                        "and warm context "
                        f"({ledger.warm_of(cur)!r}) in the ledger "
                        "(pass --baseline REF)",
                        file=sys.stderr,
                    )
                    return 2
            keys = tuple(args.keys) if args.keys else None
            violations = ledger.gate(
                base, cur, threshold=args.threshold, keys=keys
            )
            print(
                f"baseline {base.get('source')} "
                f"({base.get('digest', '?')[:8]}) vs current "
                f"{cur.get('source')} ({cur.get('digest', '?')[:8]})"
            )
            print(ledger.render_gate(violations))
            return 1 if violations else 0
    except (
        KeyError, OSError, ValueError, json.JSONDecodeError
    ) as e:
        # exit 2 (usage/input failure) — for `gate` especially, a
        # malformed file must never surface as the interpreter's
        # exit 1, which would read as "regression found"
        msg = e.args[0] if isinstance(e, KeyError) else str(e)
        print(f"tpu-tlc: {msg}", file=sys.stderr)
        return 2
    return 2


def _sim_model(args):
    """Model + constants + invariants for the ``simulate`` subcommand:
    a registry spec name (or .tla path of one), falling back to the
    spec->kernel compiler for modules outside the registry."""
    from pulsar_tlaplus_tpu.models import registry
    from pulsar_tlaplus_tpu.utils import cfg as cfgmod

    spec = args.spec
    module = (
        os.path.splitext(os.path.basename(spec))[0]
        if spec.endswith(".tla")
        else spec
    )
    cfg_path = args.config
    if cfg_path is None:
        if spec.endswith(".tla"):
            cfg_path = os.path.splitext(spec)[0] + ".cfg"
        else:
            cfg_path = os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "specs", f"{module}.cfg",
            )
    tlc_cfg = cfgmod.load(cfg_path)
    invariants = tuple(args.invariant or tlc_cfg.invariants)
    if module in registry.COMPILED:
        model, constants = registry.COMPILED[module](tlc_cfg)
        return model, constants, invariants, module
    # outside the registry: the spec->kernel compiler path
    from pulsar_tlaplus_tpu.frontend.codegen import CompiledSpec
    from pulsar_tlaplus_tpu.frontend.interp import Spec
    from pulsar_tlaplus_tpu.frontend.loader import bind_cfg
    from pulsar_tlaplus_tpu.frontend.parser import parse_file

    if not spec.endswith(".tla"):
        raise ValueError(
            f"spec {spec!r} is not in the compiled registry "
            f"(known: {sorted(registry.COMPILED)}); pass a .tla path "
            "to route through the spec->kernel compiler"
        )
    ast = parse_file(spec)
    consts = bind_cfg(ast, tlc_cfg)
    consts.pop("__string_interning__", None)
    cs = CompiledSpec(Spec(ast, consts), invariants=invariants)
    return cs, None, invariants, module


def _cmd_simulate(args) -> int:
    """Streaming walker-swarm simulation (sim/engine.py,
    docs/simulation.md): TLC's ``-simulate`` as a budgeted workload —
    thousands of vectorized random walks per dispatch, running until a
    violation or the step/walk/time budget, resumable via
    -checkpoint/-recover."""
    _jax_setup(args)
    from pulsar_tlaplus_tpu.sim.engine import StreamingSimulator

    try:
        model, constants, invariants, module = _sim_model(args)
    except (OSError, ValueError) as e:
        sys.exit(f"tpu-tlc: {e}")
    print(
        f"tpu-tlc: simulating {module} ({args.walkers} walkers, depth "
        f"{args.depth}; invariants: {list(invariants) or 'none'})"
    )
    return _run_simulation(
        args,
        lambda: StreamingSimulator(
            model,
            invariants=invariants,
            n_walkers=args.walkers,
            depth=args.depth,
            segment_len=args.segment,
            seed=args.seed,
            max_steps=args.max_steps,
            max_rounds=args.rounds,
            time_budget_s=args.time_budget,
            checkpoint_path=args.checkpoint,
            telemetry=args.telemetry,
            heartbeat_s=args.progress,
            progress=True,
            dump_path=args.sim_dump,
            dump_num=args.sim_dump_num,
        ),
        constants,
    )


def _add_sim_dump_args(sp) -> None:
    """TLC's ``-simulate file=F,num=N`` on ``check -simulate`` and on
    ``simulate``."""
    sp.add_argument(
        "-sim-dump", dest="sim_dump", default=None, metavar="F",
        help="in simulation mode: after the budget, write behaviours "
        "of the last completed round, replayed from their key "
        "streams, to F_<round>_<walker> (TLC's -simulate file=F); the "
        "budget has to end on a round boundary (a multiple of walkers "
        "x depth steps), else exit 2",
    )
    sp.add_argument(
        "-sim-dump-num", dest="sim_dump_num", type=int, default=16,
        metavar="K",
        help="with -sim-dump: how many walkers, spread evenly over "
        "the swarm and rotated by the seed (TLC's num=K; default 16)",
    )


def _add_client_args(sp) -> None:
    sp.add_argument(
        "--state-dir", default=DEFAULT_STATE_DIR,
        help="daemon state directory (socket lives at "
        "<state-dir>/serve.sock; default ~/.ptt_serve)",
    )
    sp.add_argument(
        "--socket", default=None,
        help="daemon address (overrides --state-dir): a unix socket "
        "path, or tcp://HOST:PORT for the authenticated TCP "
        "transport (pair with --token)",
    )
    sp.add_argument(
        "--token", default=None,
        help="bearer token for the TCP transport (serve --tokens; "
        "the unix socket needs none)",
    )
    sp.add_argument(
        "--retries", type=int, default=4,
        help="transport retry budget (exponential backoff + jitter "
        "on connect/transient failures; default 4)",
    )
    sp.add_argument(
        "--timeout", type=float, default=600.0,
        help="client wait/stream timeout in seconds",
    )


def _build_parser():
    p = argparse.ArgumentParser(prog="tpu-tlc")
    sub = p.add_subparsers(dest="cmd", required=True)

    ps = sub.add_parser(
        "serve",
        help="resident multi-tenant checker daemon: warmed executables "
        "for the spec registry, a FIFO job queue, and mesh "
        "time-slicing between jobs (docs/service.md)",
    )
    ps.add_argument(
        "state_dir", nargs="?", default=DEFAULT_STATE_DIR,
        help="daemon state directory (socket, queue.json, per-job "
        "dirs; default ~/.ptt_serve)",
    )
    ps.add_argument("--socket", default=None, help="override socket path")
    ps.add_argument(
        "--tcp", default=None, metavar="HOST:PORT",
        help="additionally listen on an authenticated TCP socket "
        "(port 0 = ephemeral; REQUIRES --tokens; the unix socket "
        "stays the no-auth localhost path — docs/service.md Security)",
    )
    ps.add_argument(
        "--tokens", default=None, metavar="FILE",
        help="tokens.json mapping bearer tokens to tenants "
        "(validate with scripts/check_telemetry_schema.py --tokens)",
    )
    ps.add_argument(
        "--queue-cap", type=int, default=64,
        help="global cap on alive jobs; past it submits are SHED "
        "with a typed capacity error (0 = unlimited; default 64)",
    )
    ps.add_argument(
        "--tenant-max-queued", type=int, default=16,
        help="per-tenant cap on queued jobs (0 = unlimited)",
    )
    ps.add_argument(
        "--tenant-max-running", type=int, default=0,
        help="per-tenant cap on jobs holding device slices "
        "(running + suspended; 0 = unlimited)",
    )
    ps.add_argument(
        "--tenant-max-states", type=int, default=0,
        help="per-tenant cap on the aggregate max_states budget of "
        "live jobs (0 = unlimited)",
    )
    ps.add_argument(
        "--spec", action="append", default=None,
        help="registry spec to prewarm at startup (repeatable; "
        "default: every spec with a default cfg in specs/)",
    )
    ps.add_argument(
        "--slice", type=float, default=2.0, metavar="SEC",
        help="scheduling quantum: a running job suspends at its next "
        "level boundary after SEC seconds when another job waits "
        "(default 2.0)",
    )
    ps.add_argument(
        "--maxstates", type=int, default=50_000_000,
        help="service state ceiling (also the per-job default budget)",
    )
    ps.add_argument(
        "--checkpoint-every", type=int, default=2,
        help="levels between a running job's checkpoint frames",
    )
    ps.add_argument(
        "--keep-terminal", type=int, default=512,
        help="finished-job records retained for status/result "
        "queries; oldest beyond this are pruned from the table and "
        "disk (0 = keep forever)",
    )
    ps.add_argument("-chunk", type=int, default=4096)
    ps.add_argument(
        "--no-prewarm", action="store_true",
        help="skip startup prewarm (first submit per spec pays the "
        "compile warmup)",
    )
    ps.add_argument(
        "--no-tiers", action="store_true",
        help="prewarm only the base capacity tier (faster startup, "
        "growth tiers lazy-compile)",
    )
    ps.add_argument(
        "--warm-max-bytes", type=int, default=None, metavar="BYTES",
        help="LRU byte cap on the warm-artifact store (incremental "
        "checking, docs/incremental.md; default 1 GiB; 0 disables "
        "the warm layer — no artifacts, every submit runs cold)",
    )
    ps.add_argument(
        "--recover", action="store_true",
        help="reload queue.json and resume/re-run interrupted jobs "
        "(after SIGTERM or a crash)",
    )
    ps.add_argument(
        "--drain", action="store_true",
        help="exit once the queue is idle (with --recover: complete "
        "the persisted queue, then stop)",
    )
    ps.add_argument("-cpu", action="store_true", help="force the CPU backend")
    ps.add_argument(
        "--devices", type=int, default=1, metavar="N",
        help="local device slots the scheduler runs jobs on "
        "concurrently (one worker thread + checker pool per slot; "
        "default 1 — the single-chip time-slicing shape)",
    )

    pd = sub.add_parser(
        "dispatch",
        help="fleet dispatcher: front N `serve` daemons behind one "
        "authenticated endpoint speaking the same wire protocol — "
        "load-signal routing, warm-artifact replication, failover "
        "(docs/fleet.md)",
    )
    pd.add_argument(
        "state_dir", nargs="?",
        default=os.path.expanduser("~/.ptt_fleet"),
        help="dispatcher state directory (socket, fleet_jobs.json; "
        "default ~/.ptt_fleet)",
    )
    pd.add_argument(
        "--backend", action="append", default=None, metavar="ADDR",
        help="backend daemon address (repeatable; a unix socket path "
        "or tcp://HOST:PORT — TCP backends need a tokens.json entry "
        "for the 'fleet' tenant)",
    )
    pd.add_argument(
        "--socket", default=None, help="override dispatcher socket path"
    )
    pd.add_argument(
        "--tcp", default=None, metavar="HOST:PORT",
        help="additionally listen on an authenticated TCP socket "
        "(port 0 = ephemeral; REQUIRES --tokens)",
    )
    pd.add_argument(
        "--tokens", default=None, metavar="FILE",
        help="tokens.json shared with the backends (client tokens "
        "are forwarded; the 'fleet' entry is the dispatcher's own "
        "identity)",
    )
    pd.add_argument(
        "--health-interval", type=float, default=0.5, metavar="SEC",
        help="backend health-poll period (default 0.5s)",
    )
    pd.add_argument(
        "--fail-after", type=int, default=3, metavar="N",
        help="consecutive failed polls before a backend is drained "
        "from routing (default 3)",
    )
    pd.add_argument(
        "--backend-timeout", type=float, default=10.0, metavar="SEC",
        help="per-request timeout toward a backend (default 10s)",
    )
    pd.add_argument(
        "--no-replicate", action="store_true",
        help="disable warm-artifact replication between backends "
        "(jobs still route and fail over; resubmits only warm-start "
        "on their original backend)",
    )
    pd.add_argument(
        "--recover", action="store_true",
        help="rebuild the routing table from fleet_jobs.json + a "
        "re-poll of every backend before accepting work (after a "
        "crash or kill -9): acked jobs resolve exactly-once, "
        "unconfirmed jobs on reachable backends are typed 'lost' "
        "(docs/fleet.md, Survivability)",
    )
    pd.add_argument(
        "--readmit-after", type=int, default=2, metavar="N",
        help="consecutive clean polls before a drained backend "
        "rejoins routing (default 2 — hysteresis so a flapping "
        "backend cannot thrash failover)",
    )
    pd.add_argument(
        "--hold-max", type=int, default=16, metavar="N",
        help="submits held waiting for a backend while the whole "
        "fleet is down (overflow sheds with a typed 'capacity' "
        "rejection; default 16)",
    )
    pd.add_argument(
        "--hold-s", type=float, default=10.0, metavar="SEC",
        help="how long a held submit waits for a backend to rejoin "
        "before the typed backend_unavailable rejection (default 10s)",
    )

    pj = sub.add_parser(
        "submit", help="queue a check job on the running daemon"
    )
    pj.add_argument("spec", help="registry spec name (e.g. compaction)")
    pj.add_argument("config", help=".cfg constant bindings")
    pj.add_argument(
        "-invariant", action="append", default=None,
        help="invariant to check (repeatable; default: cfg INVARIANTS)",
    )
    pj.add_argument("--maxstates", type=int, default=None)
    pj.add_argument(
        "--time-budget", type=float, default=None, metavar="SEC",
        help="cumulative engine-wall budget across scheduling slices",
    )
    pj.add_argument(
        "--mode", choices=["check", "simulate"], default="check",
        help="workload: exhaustive BFS (default) or the streaming "
        "walker swarm — simulation jobs time-slice at segment "
        "boundaries (docs/simulation.md)",
    )
    pj.add_argument(
        "--walkers", type=int, default=None,
        help="with --mode simulate: walker swarm width",
    )
    pj.add_argument(
        "--depth", type=int, default=None,
        help="with --mode simulate: steps per behavior",
    )
    pj.add_argument(
        "--segment", type=int, default=None,
        help="with --mode simulate: steps per device dispatch",
    )
    pj.add_argument(
        "--sim-seed", dest="sim_seed", type=int, default=None,
        help="with --mode simulate: PRNG seed (deterministic stream)",
    )
    pj.add_argument(
        "--sim-steps", dest="sim_steps", type=int, default=None,
        help="with --mode simulate: total step budget across the "
        "swarm (default: one depth-round)",
    )
    pj.add_argument(
        "--priority", type=int, default=0, metavar="N",
        help="scheduling priority (higher first; a waiting higher-"
        "priority job preempts a running lower one at its next "
        "level boundary; clamped to [-9, 9] at the daemon; "
        "default 0)",
    )
    pj.add_argument(
        "--deadline-s", type=float, default=None, metavar="SEC",
        help="wall-clock deadline from submit; past it the job is "
        "cancelled with stop_reason=deadline (exit 3, no verdict)",
    )
    pj.add_argument(
        "--no-warm", action="store_true",
        help="opt this job out of warm-start reuse AND artifact "
        "harvesting: always a full cold recheck "
        "(docs/incremental.md)",
    )
    pj.add_argument(
        "--submit-id", default=None, metavar="ID",
        help="idempotency key: a retried submit with the same id "
        "returns the SAME job instead of enqueueing twice "
        "(auto-generated when omitted)",
    )
    pj.add_argument(
        "--wait", action="store_true",
        help="block until the job finishes; exit code mirrors `check`",
    )
    pj.add_argument(
        "--watch", action="store_true",
        help="stream the job's relayed telemetry until it finishes",
    )
    _add_client_args(pj)

    pst = sub.add_parser(
        "status", help="job table (or one job) from the daemon"
    )
    pst.add_argument("job_id", nargs="?", default=None)
    _add_client_args(pst)

    pw = sub.add_parser(
        "watch", help="stream a job's telemetry (level progress, "
        "heartbeat, per-slice run headers) until it finishes",
    )
    pw.add_argument("job_id")
    _add_client_args(pw)

    pca = sub.add_parser("cancel", help="cancel a queued/running job")
    pca.add_argument("job_id")
    _add_client_args(pca)

    ptr = sub.add_parser(
        "trace",
        help="convert telemetry stream(s) into Perfetto-loadable "
        "Chrome trace JSON: BFS levels, ckpt stalls, sweep chunks, "
        "daemon job slices + context-switch gaps on one timeline — "
        "plus fleet dispatcher hops and trace_id flow arrows when a "
        "dispatch.jsonl rides along (r22)",
    )
    ptr.add_argument(
        "stream", nargs="+",
        help="telemetry JSONL file(s): engine runs, a daemon's "
        "service.jsonl, per-job jobs/<id>/events.jsonl, a fleet "
        "dispatcher's dispatch.jsonl — any mix; pass the dispatch "
        "stream plus every backend's service.jsonl to stitch one "
        "fleet timeline with cross-backend flow arrows",
    )
    ptr.add_argument(
        "-o", "--output", default="trace.json",
        help="output trace file (default trace.json)",
    )

    pm = sub.add_parser(
        "metrics",
        help="Prometheus text metrics: scrape the live daemon's "
        "`metrics` verb, or derive the same families from a stream "
        "tail (--stream)",
    )
    pm.add_argument(
        "--stream", default=None, metavar="FILE",
        help="derive metrics from this telemetry JSONL instead of "
        "scraping the daemon",
    )
    pm.add_argument(
        "--aggregate", action="store_true",
        help="against a fleet dispatcher: scrape every live backend "
        "too and re-emit its families under a backend label beside "
        "the fleet rollups + ptt_fleet_*_seconds histograms",
    )
    _add_client_args(pm)

    pt = sub.add_parser(
        "top",
        help="live dashboard: job table, per-job rate sparklines, "
        "heartbeat status line — polling the daemon or tailing a "
        "stream (--stream)",
    )
    pt.add_argument(
        "--stream", action="append", default=None, metavar="FILE",
        help="tail telemetry JSONL file(s) instead of polling the "
        "daemon (repeatable: pass service.jsonl plus "
        "jobs/*/events.jsonl for per-job sparklines)",
    )
    pt.add_argument(
        "--interval", type=float, default=2.0, metavar="SEC",
        help="refresh interval (default 2s)",
    )
    pt.add_argument(
        "--once", action="store_true",
        help="render one frame (no ANSI clear) and exit",
    )
    pt.add_argument(
        "--dispatch", action="store_true",
        help="fleet flight deck: poll a dispatcher instead of a "
        "daemon — per-backend health/score/stickiness table, fleet "
        "job rollups, rate sparklines, histogram-derived p50/p99 "
        "latency columns (one ping + one aggregate scrape per tick)",
    )
    _add_client_args(pt)

    pl = sub.add_parser(
        "ledger",
        help="cross-run regression ledger: ingest BENCH_*.json "
        "artifacts + telemetry streams into an append-only JSONL "
        "ledger, render trajectories and deltas, gate regressions "
        "(docs/observability.md)",
    )
    pl.add_argument(
        "--ledger", default="LEDGER.jsonl", metavar="FILE",
        help="ledger file (append-only JSONL; default ./LEDGER.jsonl)",
    )
    lsub = pl.add_subparsers(dest="ledger_cmd", required=True)
    pla = lsub.add_parser(
        "add", help="ingest artifacts/streams (idempotent by digest)"
    )
    pla.add_argument(
        "files", nargs="+",
        help="BENCH_*.json artifacts and/or telemetry .jsonl streams",
    )
    pll = lsub.add_parser(
        "list", help="trajectory table of every ledger record"
    )
    pll.add_argument(
        "--key", default=None,
        help="only records with this config key",
    )
    pls = lsub.add_parser("show", help="every key of one record")
    pls.add_argument(
        "ref", help="digest prefix, source name, 1-based index, or a "
        "file path (ingested on the fly)",
    )
    plc = lsub.add_parser(
        "compare", help="per-key delta table between two runs"
    )
    plc.add_argument("ref_a", help="baseline record REF (or file path)")
    plc.add_argument("ref_b", help="current record REF (or file path)")
    plg = lsub.add_parser(
        "gate",
        help="exit 1 when the current run regresses past the "
        "threshold vs its baseline (same config key by default)",
    )
    plg.add_argument(
        "--current", default=None,
        help="current record REF or file path (default: newest "
        "ledger record)",
    )
    plg.add_argument(
        "--baseline", default=None,
        help="baseline record REF or file path (default: newest "
        "earlier record with the same config key)",
    )
    plg.add_argument(
        "--threshold", type=float, default=0.1, metavar="REL",
        help="relative regression tolerance (default 0.10 = 10%%)",
    )
    plg.add_argument(
        "--keys", nargs="*", default=None,
        help="gated keys (default: every known gate key; "
        "machine-independent choices: dispatches_per_level "
        "work_units_per_state)",
    )
    plg.add_argument(
        "--profile", default="same", metavar="CTX",
        help="baseline profile context (default 'same': tuned gates "
        "against tuned, default against default): 'none' = only "
        "untuned baselines (is tuning a regression vs hand "
        "defaults?), 'any' = ignore profile context, or a "
        "profile-sig prefix",
    )

    psim = sub.add_parser(
        "simulate",
        help="streaming walker-swarm simulation (TLC -simulate, "
        "reborn: thousands of vectorized random walks per dispatch "
        "under step/walk/time budgets, resumable and deterministic "
        "given -seed; docs/simulation.md)",
    )
    psim.add_argument(
        "spec",
        help="registry spec name (e.g. compaction) or a .tla path",
    )
    psim.add_argument(
        "-config", default=None,
        help=".cfg constant bindings (default: specs/<spec>.cfg)",
    )
    psim.add_argument(
        "-invariant", action="append", default=None,
        help="invariant to check (repeatable; default: cfg INVARIANTS)",
    )
    psim.add_argument(
        "-walkers", type=int, default=None, metavar="N",
        help="walker swarm width (default 1024)",
    )
    psim.add_argument(
        "-depth", type=int, default=64,
        help="steps per behavior before walkers restart (TLC "
        "-simulate depth; default 64, where TLC's -depth defaults to 100)",
    )
    psim.add_argument(
        "-segment", type=int, default=None, metavar="STEPS",
        help="steps per device dispatch (clamped to a divisor of "
        "-depth; default min(depth, 32))",
    )
    psim.add_argument(
        "-seed", type=int, default=0,
        help="PRNG seed — the whole walk stream is deterministic "
        "given it (default 0)",
    )
    psim.add_argument(
        "-max-steps", dest="max_steps", type=int, default=None,
        help="stop after this many random steps across the swarm",
    )
    psim.add_argument(
        "-rounds", type=int, default=None, metavar="N",
        help="stop after N completed behavior rounds per walker",
    )
    psim.add_argument(
        "-time-budget", dest="time_budget", type=float, default=None,
        metavar="SEC", help="wall-clock budget",
    )
    _add_sim_dump_args(psim)
    psim.add_argument(
        "-checkpoint", default=None,
        help="checkpoint file (.npz): segment-boundary frames; "
        "SIGTERM/SIGINT exit resumably; resume the IDENTICAL walk "
        "stream with -recover",
    )
    psim.add_argument(
        "-recover", action="store_true",
        help="resume from -checkpoint",
    )
    psim.add_argument(
        "-telemetry", metavar="FILE",
        help="write the v11 run-event stream (run_header.mode="
        "simulate, cumulative `sim` records) to this file",
    )
    psim.add_argument(
        "-progress", type=float, default=None, metavar="SEC",
        help="heartbeat line every SEC seconds (states, steps, "
        "walks/s EWMA — zero extra device syncs)",
    )
    psim.add_argument(
        "-cpu", action="store_true", help="force the CPU backend"
    )

    pc = sub.add_parser("check", help="exhaustive BFS model checking")
    pc.add_argument("spec", help="path to the .tla module (module 'compaction')")
    pc.add_argument("-config", help=".cfg file (defaults to SPEC's .cfg)")
    pc.add_argument(
        "-workers",
        type=_positive_or_tpu,
        default="tpu",
        help="'tpu' (default: single-chip device engine) or a worker "
        "count N (TLC parity: maps to '-sharded N' mesh-sharded "
        "checking over N devices; an error when the host has fewer)",
    )
    pc.add_argument(
        "-sharded",
        type=int,
        default=0,
        metavar="N",
        help="run mesh-sharded over N devices",
    )
    pc.add_argument(
        "-slices",
        type=int,
        default=1,
        metavar="S",
        help="with -sharded: arrange the N devices as S slices (2-D "
        "dcn x ici mesh with hierarchical fingerprint routing)",
    )
    pc.add_argument(
        "-sharded-dedup",
        choices=["sort", "hash"],
        default="sort",
        help="sharded visited-set structure (default: sorted columns)",
    )
    pc.add_argument(
        "-fuse",
        choices=["level", "stage"],
        default="level",
        help="device-engine dispatch fusion: 'level' (default — one "
        "fused megakernel dispatch per BFS level, with shallow ramp "
        "levels batched several-per-dispatch) or 'stage' (the "
        "per-stage dispatch chain)",
    )
    pc.add_argument(
        "-fuse-group",
        dest="fuse_group",
        type=int,
        default=None,
        metavar="G",
        help="with -fuse level: max ramp levels batched into one "
        "dispatch (default: auto from the frontier size, up to 8; "
        "1 disables ramp batching)",
    )
    pc.add_argument(
        "-sweep-group",
        dest="sweep_group",
        type=int,
        default=None,
        metavar="G",
        help="liveness edge sweep: chunks fused per device dispatch "
        "(default: auto from HBM headroom) — the host<->device round "
        "trip amortizes across the group",
    )
    pc.add_argument(
        "-sharded-engine",
        choices=["device", "host"],
        default="device",
        help="sharded implementation: 'device' = fully device-resident "
        "(all_to_all candidate routing inside the jitted step; "
        "supports -slices 2-D meshes and -checkpoint/-recover; "
        "default) or 'host' = the round-2 host-staged driver (needed "
        "only for -sharded-dedup hash)",
    )
    pc.add_argument(
        "-invariant",
        action="append",
        default=None,
        help="invariant name to check (repeatable; default: cfg INVARIANTS)",
    )
    pc.add_argument(
        "-nodeadlock",
        action="store_true",
        help="disable deadlock checking (TLC: -deadlock)",
    )
    pc.add_argument(
        "-property",
        dest="liveness_property",
        metavar="NAME",
        help="check a liveness property (e.g. Termination) instead of invariants",
    )
    pc.add_argument(
        "-fairness",
        choices=["none", "wf_next"],
        default="none",
        help="fairness assumption for -property (default: none, like the raw Spec)",
    )
    pc.add_argument(
        "-simulate",
        type=int,
        default=0,
        metavar="N",
        help="simulation mode: N random walkers instead of exhaustive BFS",
    )
    pc.add_argument(
        "-depth", type=int, default=64,
        help="with -simulate: steps per behaviour before the walkers "
        "restart (default 64; TLC's -depth defaults to 100)",
    )
    pc.add_argument(
        "-segment", type=int, default=None, metavar="STEPS",
        help="with -simulate: steps per device dispatch (clamped to "
        "a divisor of -depth)",
    )
    pc.add_argument(
        "-sim-seed", dest="sim_seed", type=int, default=0,
        help="with -simulate: PRNG seed (deterministic walk stream)",
    )
    pc.add_argument(
        "-sim-steps", dest="sim_steps", type=int, default=None,
        help="with -simulate: total step budget across the swarm "
        "(default: one depth-round, the legacy one-shot semantics)",
    )
    _add_sim_dump_args(pc)
    pc.add_argument(
        "-metrics", help="write per-level JSONL metrics to this file"
    )
    pc.add_argument(
        "-telemetry",
        metavar="FILE",
        help="write the structured run-event stream (versioned JSONL: "
        "run header, per-level progress, per-flush fpset metrics, "
        "checkpoint frames, recovery/fault events, final result) to "
        "this file; see docs/observability.md",
    )
    pc.add_argument(
        "-progress",
        type=float,
        default=None,
        metavar="SEC",
        help="TLC-style periodic progress line every SEC seconds "
        "(default off): states generated/distinct, frontier depth, "
        "states/sec, fpset occupancy, and ETA-to-capacity — reported "
        "from the last fetched stats snapshot, adding zero device "
        "syncs",
    )
    pc.add_argument(
        "-xprof",
        metavar="DIR",
        help="capture a JAX profiler trace into DIR around the "
        "-xprof-levels window of the device engine (real-chip runs; "
        "-profile traces the WHOLE check instead)",
    )
    pc.add_argument(
        "-xprof-levels",
        metavar="LO:HI",
        default=None,
        help="BFS level window for -xprof (e.g. 6:7; default: the "
        "whole run)",
    )
    pc.add_argument(
        "-checkpoint",
        help="checkpoint file (.npz): level-boundary frames are written "
        "atomically every few levels; SIGTERM/SIGINT checkpoint at the "
        "next boundary and exit resumably; resume with -recover",
    )
    pc.add_argument(
        "-hbm-budget",
        dest="hbm_budget",
        metavar="BYTES",
        default=None,
        help="device-memory byte budget for the tiered state store "
        "(e.g. 7.5G, 512M; PTT_HBM_BUDGET env works too): visited "
        "keys and aged rows/trace logs past the budget spill to host "
        "RAM (and, with -checkpoint, to disk) through the "
        "sieve-and-compress pipeline — breaks the HBM ceiling on "
        "max_states (docs/memory.md)",
    )
    pc.add_argument(
        "-no-spill-compress",
        dest="no_spill_compress",
        action="store_true",
        help="spill raw planes instead of delta+zlib (trades link "
        "bytes for encode CPU; docs/memory.md)",
    )
    pc.add_argument(
        "-recover", action="store_true", help="resume from -checkpoint"
    )
    pc.add_argument(
        "-engine",
        choices=["device", "host"],
        default="device",
        help="non-sharded engine: 'device' (fully device-resident BFS, "
        "engine/device_bfs.py — the bench engine, with checkpoint/"
        "recover, HBM-exhaustion recovery, and preemption-safe "
        "shutdown; default) or 'host' (the host-driver engine/bfs.py, "
        "kept for disk-backed state logs and hash dedup)",
    )
    pc.add_argument(
        "-cpu", action="store_true", help="force the CPU backend"
    )
    pc.add_argument(
        "-profile",
        metavar="DIR",
        help="capture a JAX profiler trace of the whole check into DIR "
        "(inspect with TensorBoard / Perfetto)",
    )
    pc.add_argument(
        "-interp",
        action="store_true",
        help="force the generic-interpreter path (host BFS; works for any "
        "spec in the supported TLA+ subset, no compiled model needed)",
    )
    pc.add_argument(
        "-compile",
        dest="force_compile",
        action="store_true",
        help="force the spec->kernel compiler path (TPU kernels compiled "
        "from the .tla, bypassing any hand-written model; the path of "
        "every module with no hand-written model).  After the verdict "
        "one 'Compiled from the .tla: ...' line names the widths; a spec "
        "outside the compilable subset is checked by the interpreter "
        "instead and the check says 'falling back to the generic "
        "interpreter', which the benchmark's cell cli-compiled counts "
        "as wrong",
    )
    pc.add_argument("-chunk", type=int, default=4096)
    pc.add_argument("-maxstates", type=int, default=200_000_000)
    return p


def main(argv=None):
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv[:1] != ["check"]:
        # the daemon and the client verbs (which never import jax)
        args = _build_parser().parse_args(argv)
        return {
            "serve": _cmd_serve,
            "dispatch": _cmd_dispatch,
            "simulate": _cmd_simulate,
            "submit": _cmd_submit,
            "status": _cmd_status,
            "watch": _cmd_watch,
            "cancel": _cmd_cancel,
            "ledger": _cmd_ledger,
            "trace": _cmd_trace,
            "metrics": _cmd_metrics,
            "top": _cmd_top,
        }[args.cmd](args)
    # host spans of one check (obs/spans.py; with no profiler trace
    # running they cost nothing): ptt:check around ptt:cli.parse,
    # .build (or, through the spec->kernel compiler, .codegen),
    # .engine_init, .report and the engine's own ptt:run
    from pulsar_tlaplus_tpu.obs import spans

    with spans.span("check"):
        with spans.span("cli.parse"):
            args = _build_parser().parse_args(argv)
        return _cmd_check(args)


def _cmd_check(args) -> int:
    from pulsar_tlaplus_tpu.obs import spans

    args.xprof_window = None
    if args.xprof_levels:
        from pulsar_tlaplus_tpu.obs.telemetry import parse_level_window

        try:
            args.xprof_window = parse_level_window(args.xprof_levels)
        except ValueError as e:
            sys.exit(f"tpu-tlc: -xprof-levels: {e}")
    if args.profile and args.xprof:
        # JAX allows one active profiler trace: the whole-check trace
        # would collide with the level window mid-run, aborting a run
        # that may be hours in
        sys.exit(
            "tpu-tlc: -profile and -xprof are mutually exclusive "
            "(both drive jax.profiler; pick the whole-check trace OR "
            "the level window)"
        )
    _jax_setup(args)
    if args.profile:
        import atexit

        import jax

        jax.profiler.start_trace(args.profile)
        atexit.register(jax.profiler.stop_trace)

    from pulsar_tlaplus_tpu.utils import cfg as cfgmod
    from pulsar_tlaplus_tpu.utils.render import render_trace

    spec_path = args.spec
    module = os.path.splitext(os.path.basename(spec_path))[0]
    cfg_path = args.config or os.path.splitext(spec_path)[0] + ".cfg"
    if not os.path.exists(cfg_path):
        sys.exit(f"tpu-tlc: config file not found: {cfg_path}")
    with spans.span("cli.parse"):
        tlc_cfg = cfgmod.load(cfg_path)
    invariants = tuple(args.invariant or tlc_cfg.invariants)
    if isinstance(args.workers, int) and not args.sharded:
        # TLC parity: -workers N is worker parallelism; here that is
        # mesh sharding over N devices.  A request for more devices
        # than the host has is an error, never a silent cap: a user
        # who asked for 4 chips must not get a 1-chip run
        import jax

        have = len(jax.devices())
        if args.workers > have:
            sys.exit(
                f"tpu-tlc: -workers {args.workers} needs "
                f"{args.workers} devices; this host has {have}"
            )
        if args.workers == 1:
            # one worker IS the single-chip engine: identical
            # semantics, without the sharded engine's routing and
            # per-shard bookkeeping on a singleton mesh
            args.workers = "tpu"
        else:
            print(
                f"tpu-tlc: note: -workers {args.workers} maps to "
                f"-sharded {args.workers} (mesh-sharded checking)"
            )
            args.sharded = args.workers
    if not args.sharded and (
        args.slices > 1 or args.sharded_dedup != "sort"
    ):
        sys.exit("tpu-tlc: -slices/-sharded-dedup require -sharded N")

    from pulsar_tlaplus_tpu.models import registry

    if args.interp:
        return _check_interp(args, module, spec_path, tlc_cfg, invariants)
    if args.force_compile or module not in registry.COMPILED:
        out = _check_compiled_spec(
            args, module, spec_path, tlc_cfg, invariants
        )
        if out is not None:
            return out
        if module not in registry.COMPILED:
            return _check_interp(
                args, module, spec_path, tlc_cfg, invariants
            )

    try:
        with spans.span("cli.build"):
            model, constants = registry.COMPILED[module](tlc_cfg)
    except ValueError as e:
        sys.exit(f"tpu-tlc: {e}")
    unknown = [i for i in invariants if i not in model.invariants]
    if unknown:
        sys.exit(f"tpu-tlc: unknown invariant(s): {unknown}")
    print(
        f"tpu-tlc: checking {module} @ {cfg_path} "
        f"(state width {model.layout.total_bits} bits, "
        f"{model.A} successor lanes; {_checking_what(args, invariants)})"
    )
    t0 = time.time()
    return _dispatch_engines(args, model, constants, invariants, tlc_cfg, t0)


if __name__ == "__main__":
    sys.exit(main())
