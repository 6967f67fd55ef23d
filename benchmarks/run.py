#!/usr/bin/env python3
"""The benchmark's command: one cell, one seed, one window, one result.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the chips the cell asks for. Set-up (imports, weights from the
seed on the device, ``serve.run``, one warm request for every shape the
schedule holds) is timed as ``setup_s``; then the open-loop schedule runs:
ramp-in, the window, a cut at its close. Then the device's peak is read, the
program's state is freed and the output check runs (``harness/check.py``).
The last line of standard output is the result; the numbers compared are
the last lines of standard error. ``--control 1`` (never set by the driver)
also puts the int8 control through the same comparison and limits and
prints ``control_correct``, which has to read false.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")
# capture of the traced run: from this share of the window, for this long
TRACE_START_SHARE, TRACE_SECONDS = 0.1, 10.0


def log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


def find_chips(chips: int):
    """The accelerator, or no result: a CPU is not measured."""
    import jax

    from harness import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"no accelerator: JAX found {devs[0].platform!r}; the benchmark "
            "measures a TPU and prints no result elsewhere"
        )
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    return devs[:chips], peaks.peaks_for(devs[0].device_kind)


def take_trace(run_probe, t_open: float, t_close: float, clock):
    """Captures a stretch of the window with the profiler; returns its
    two ends on the host's clock."""
    import jax

    length = min(TRACE_SECONDS, 0.6 * (t_close - t_open))
    start = t_open + TRACE_START_SHARE * (t_close - t_open)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    os.makedirs(TRACE_DIR, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    time.sleep(max(0.0, start - clock()))
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    c0 = clock()
    time.sleep(length)
    c1 = clock()
    jax.profiler.stop_trace()
    run_probe["capture"] = (c0, c1)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, peaks,
             control: bool = False) -> dict:
    """Everything after the look for a chip. Returns the result object."""
    import jax

    from ray_tpu.util.compile_cache import configure_compile_cache

    from harness import check, metrics, probes, served, spec, traffic
    from harness import trace as trace_mod

    clock = time.perf_counter
    stages = {"chip_found_s": clock() - T_START}  # where set-up's time goes
    configure_compile_cache()  # the program's own rule for the directory
    compiles = probes.CompileCounter()
    cfg, mix = cell.cfg, cell.mix
    params = spec.load_family(cfg, cell.base).make_weights(cfg, seed)
    stages["weights_made_s"] = clock() - T_START
    schedule = traffic.schedule(mix, seconds)
    engine_probes = probes.EngineProbes(clock)
    probe: dict = {}
    with served.Served(cfg, params, engine_probes, cell.base) as sv:
        stages["serving_s"] = clock() - T_START
        warmed = sv.warm_up(schedule, seed, cfg["deployment"]["page_size"])
        setup_s = clock() - T_START
        log(phase="set-up", setup_s=setup_s, warm_requests=len(warmed),
            compiles=compiles.compiles, cache_hits=compiles.cache_hits,
            compile_s=compiles.seconds, **stages)
        at_open = {}

        def opened():
            at_open["compiles"] = compiles.compiles
            gc.collect()

        clients, t_open, t_close = sv.drive(
            schedule, seed, seconds, float(mix["arrivals"]["ramp_in_s"]),
            clock, at_open=opened,
            during=(
                (lambda a, b: take_trace(probe, a, b, clock)) if trace else None
            ),
        )
        window_compiles = compiles.compiles - at_open["compiles"]
        drain_s = clock() - t_close
        codes = sv.router.stats().get("codes")
        cut_report = sv.cut_report
    stats_dev = devices[0].memory_stats() or {}
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    )
    engine_probes.release_engines()
    gc.collect()
    log(phase="window", seconds=seconds, drain_s=drain_s,
        window_compiles=window_compiles, codes=codes, **cut_report,
        bytes_in_use_after=stats_dev.get("bytes_in_use"))

    run = metrics.Run(
        cfg=cfg, mix=mix, base=cell.base, peaks=peaks, t_open=t_open,
        t_close=t_close, setup_s=setup_s, clients=clients,
        decode_log=engine_probes.decode_log,
        prefill_log=engine_probes.prefill_log,
        window_compiles=window_compiles, memory_peak_bytes=peak or None,
        capture=probe.get("capture"),
    )
    if trace:
        t0 = clock()
        run.trace = trace_mod.reduce_xplane(trace_mod.newest_xplane(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log(phase="trace", reduce_s=clock() - t0, window_s=run.trace.window_s,
            busy_s=run.trace.busy_s, programs=run.trace.programs,
            host_spans=run.trace.host_spans)

    # -- the output check, on what the window's clients received ---------
    t0 = clock()
    tok = served.IdTokenizer()
    records = []
    for c in clients:
        if c.sent is None:
            continue
        ids = [tok.encode(p)[0] for p in c.pieces if len(p) == 1]
        records.append({
            "index": c.request.index, "prompt": c.prompt.tolist(),
            "prompt_len": c.request.prompt_len, "max_new": c.request.max_new,
            "ids": ids,
            "pieces_bad": sum(1 for p in c.pieces if len(p) != 1),
            "finished": c.done is not None and not c.error and not c.cut,
            "error": c.error,
        })
    compared = check.compare(
        cfg, params, records, seed, cell.cell["check"],
        spec.load_reference(cfg, cell.base), control=control, log=log,
    )
    compared["cut_unexplained"] = {
        "value": cut_report["unexplained"], "limit": 0}
    compared["window_compiles"] = {"value": window_compiles, "limit": 0}
    correct = check.verdict(compared)
    log(phase="check", seconds=clock() - t0,
        finished=sum(1 for r in records if r["finished"]))

    # -- the result ---------------------------------------------------------
    if trace:
        values = {}
        for m in cell.per_layer:
            v = spec.load_reader(m["name"], cell.base)(run)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = metrics.end_to_end(run)
        values = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if e2e.get(m["name"]) is not None
        }
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak,
    }
    result = {
        "correct": bool(correct),
        "attempted": len(records),
        "failed": sum(1 for r in records if r["error"]),
        "metrics": values,
        "device": device,
    }
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.device_ops],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps],
        }
    result["compared"] = compared
    where = f"{device['platform']}/{device['kind']}/x{device['count']}"
    for name, entry in compared.items():
        bound = (
            f"limit {entry['limit']}" if "limit" in entry
            else f"at least {entry['at_least']}" if "at_least" in entry
            else "not held to a limit"
        )
        if "over" in entry:
            bound += f", gaps over {entry['over']}"
        print(f"compared {name}: {entry['value']} ({bound}) on {where}",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import spec

    cell = spec.load_cell(args.workload)
    devices, peaks = find_chips(cell.chips)
    result = run_cell(
        cell, args.seed, args.seconds, bool(args.trace), devices, peaks,
        control=bool(args.control),
    )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # replica threads of the in-process runtime are daemons; nothing is left
    os._exit(code)
