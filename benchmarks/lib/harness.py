"""One run of one cell: set-up, the measured window through
``Trainer.train()``, the optional traced stretch, then `correct` against the
plain reference. ``run.py`` is the command; tests call ``run_cell`` with the
look for a chip switched off."""

from __future__ import annotations

import functools
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

from benchmarks.lib import cells, peaks, refrun, trace as trace_lib, traffic as traffic_lib
from benchmarks.lib.recorder import FirstSteps


class NoResult(RuntimeError):
    """The run cannot be reported: exit non-zero, print no result line."""


def check_devices(cell, require_tpu: bool):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoResult(f"JAX found no usable backend: {e}") from e
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise NoResult(
            f"found platform {platform!r} ({len(devices)} x {devices[0].device_kind!r}), not a TPU: "
            "this benchmark reports device numbers and has no CPU fallback")
    if len(devices) < cell.chips:
        raise NoResult(f"cell {cell.name} needs {cell.chips} chip(s), JAX found {len(devices)}")
    return devices[: cell.chips]


def place_compile_cache() -> str:
    """``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` places it. The
    checkout's own directory is never capped: it holds what this checkout's
    cells compile and nothing else, and a cell's next run has to find every
    program of the last one there. A cap set from outside for some other
    directory (``JAX_COMPILATION_CACHE_MAX_SIZE``; the chip tool's machines
    bring 192 MiB) makes jax evict the least recently used entry, which is the
    one the next run asks for first: the four-chip LM cell's programs weigh
    148 MB a run, 228 MB with a stand-in's, and the calibration compiled every
    seed anew under it (PERF.md section 6, PR 27). A directory placed from
    outside keeps whatever cap came with it."""
    import jax
    from distributed_training_pytorch_tpu.utils import enable_compile_cache

    path = enable_compile_cache()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def build_mesh(traffic: dict, devices):
    from distributed_training_pytorch_tpu.parallel.mesh import MeshConfig, create_mesh

    axes = traffic.get("mesh") or {"data": len(devices)}
    if len(devices) == 1 and all(v == 1 for v in axes.values()):
        return create_mesh(devices=devices)
    return MeshConfig(**axes).build(devices=devices)


def make_weights(ref, cfg, traffic, seed, trainer):
    """The benchmark's own weights: made on the device from the seed in one
    jitted call by the reference's published initialisation, in the program's
    tree and placement, and laid into ``trainer.state``. The parameters that
    ``Trainer.__init__`` initialised are freed first, so the call's peak is
    the optimizer's state plus the one tree it makes, and nothing of the
    benchmark's stays on the device. Returns the call: the same compiled
    program gives the same bits again to whoever takes a change from the
    start (made again inside another program they differ: every element of
    a random leaf by part of a unit in the last place on the CPU, where the
    compiler takes the difference from the unrounded product)."""
    import jax

    target = trainer.state.params
    shardings = jax.tree.map(lambda x: x.sharding, target)

    def make(key):
        return ref.to_program(ref.init_params(cfg, traffic, key), cfg)

    want = jax.tree.map(lambda x: (x.shape, x.dtype), target)
    got = jax.tree.map(lambda x: (x.shape, x.dtype), jax.eval_shape(make, jax.random.key(seed)))
    if want != got:
        raise NoResult(f"the reference's layout of the weights is not the program's:\n{want}\nvs\n{got}")
    for leaf in jax.tree.leaves(target):
        leaf.delete()
    make = functools.partial(jax.jit(make, out_shardings=shardings), jax.random.key(seed))
    trainer.state = trainer.state.replace(params=make())
    return make


def device_bytes(arrays, device) -> int:
    """Bytes that ``arrays`` hold on ``device``, by each one's shard there."""
    return sum(math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
               for x in arrays if device in x.sharding.device_set)


def memory_peak_bytes(devices) -> int | None:
    """Peak on the fullest chip. This runtime keeps a program's temporaries
    in ``bytes_reserved`` and outside ``peak_bytes_in_use`` (PERF.md section
    7), so where a reserved figure is reported the peak is the larger of the
    allocator's own peak and the largest in-use + reserved seen."""
    best = None
    for d in devices:
        stats = d.memory_stats() or {}
        if not stats:
            continue
        cands = [stats.get("peak_bytes_in_use", 0)]
        if "peak_bytes_reserved" in stats:
            cands.append(stats.get("bytes_in_use", 0) + stats["peak_bytes_reserved"])
        if "bytes_reserved" in stats:
            cands.append(stats.get("bytes_in_use", 0) + stats["bytes_reserved"])
        best = max(best or 0, *cands)
    return best


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *, require_tpu: bool = True,
             bench_file: str | None = None, data_dirs=None, t_start: float | None = None,
             fault=None, stand_in=None, extra_readings: bool = False) -> dict:
    """Returns the result object. ``fault`` (tests) is called with the built
    trainer before warm-up and may break the timed path. ``stand_in`` (tests,
    calibration) puts the reference, in the configuration's control precision
    ("control") or with a planted fault ("half_batch", "no_exchange"), in the program's place."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = cells.load_cell(workload, bench_file, data_dirs)
    cfg, traffic = cell.config, cell.traffic
    devices = check_devices(cell, require_tpu)
    place_compile_cache()
    system, ref = importlib.import_module(cfg["system"]), importlib.import_module(cfg["reference"])
    system.prepare()
    data_seed, weight_seed, trainer_seed = traffic_lib.sub_seeds(seed)
    data = traffic_lib.make_data(cfg, traffic, data_seed)
    workdir = tempfile.mkdtemp(prefix="bench_run_")  # under TMPDIR: run artefacts only, never a cache
    try:
        return _run(cell, devices, system, ref, data, weight_seed, trainer_seed, workdir, t_start,
                    seconds=seconds, trace=trace, fault=fault, stand_in=stand_in, extra_readings=extra_readings)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _run(cell, devices, system, ref, data, weight_seed, trainer_seed, workdir, t_start, *,
         seconds, trace, fault, stand_in, extra_readings):
    import jax

    cfg, traffic = cell.config, cell.traffic

    from distributed_training_pytorch_tpu.telemetry import Telemetry

    stamps = {"devices_found": time.perf_counter() - t_start}

    def stamp(name):
        stamps[name] = time.perf_counter() - t_start

    telemetry = None
    if trace:  # goodput buckets only: the step program stays the untraced run's
        telemetry = Telemetry(stats=False, goodput=True, mfu=False, anomaly=None, memory=False,
                              straggler=False, heartbeat_every_s=0.0)
    trainer = system.build(cfg, traffic, data, seed=trainer_seed, mesh=build_mesh(traffic, devices),
                           telemetry=telemetry, save_folder=workdir)
    stamp("trainer_built")
    if len(trainer.train_dataloader) != traffic["steps_per_epoch"]:
        raise NoResult(f"the loader gives {len(trainer.train_dataloader)} steps an epoch, "
                       f"the traffic file says {traffic['steps_per_epoch']}")
    start_again = make_weights(ref, cfg, traffic, weight_seed, trainer)
    jax.block_until_ready(trainer.state)
    stamp("weights_made")
    if fault is not None:
        fault(trainer)
    recorder = FirstSteps(trainer, ref, cfg, traffic["check_steps"], start_again)
    del start_again

    epoch = [0]

    def run_slice():
        # One slice of the loop: train() for one more epoch. The schedule was
        # built for the fixed long run; only the stopping point moves
        # (chip_smoke.py:phase_leg_a's seam). train() leaves cur_epoch at the
        # epoch it last ran, so the next one is set as a resume would set it.
        trainer.cur_epoch, trainer.max_epoch = epoch[0], epoch[0] + 1
        trainer.train()
        epoch[0] += 1

    # Warm-up: the first slice compiles (or loads) every program of the cell
    # and is the one the recorder reads; the second runs them all again warm.
    run_slice()
    stamp("first_slice")
    run_slice()
    prog = recorder.result()
    problems = system.expect_kernels(cfg, devices[0].platform == "tpu")
    traced_before = dict(trainer.engine.trace_counts)

    # The traced stretch (--trace 1 only): the profiler around whole steady
    # slices, before the window, so that starting and stopping the profiler
    # is in no number the window gives.
    trace_info = None
    if trace:
        logdir = os.path.join(workdir, "trace")
        jax.block_until_ready(trainer.state)
        # Never the Python tracer (on by default): it hooks every thread and stalled
        # VGG16's loader by seconds. Host events (the benchmark's own spans among
        # them) only where the mix asks: PJRT's host-side transpose of a uint8 image
        # batch emits 1.2 million of them a transfer and runs 10x slower traced.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1 if traffic.get("trace_host_events", True) else 0
        jax.profiler.start_trace(logdir, profiler_options=options)
        t_trace = time.perf_counter()
        for _ in range(traffic.get("trace_slices", 1)):
            with jax.profiler.TraceAnnotation("bench.slice"):
                run_slice()
        jax.block_until_ready(trainer.state)
        trace_info = (logdir, time.perf_counter() - t_trace)
        jax.profiler.stop_trace()
        stamp("traced")

    # The window: whole slices until the first boundary at or after --seconds,
    # all steps over all time between two syncs on the trainer's state.
    steps_before = len(trainer.step_losses)
    goodput_before = dict(trainer.goodput.buckets) if trainer.goodput is not None else None
    jax.block_until_ready(trainer.state)
    # What the first chip holds as the window opens, and the program's state in
    # it: the rest is what the run costs the device beyond the program.
    held = {"live_bytes_at_open": device_bytes(jax.live_arrays(), devices[0]),
            "state_bytes_at_open": device_bytes(jax.tree.leaves(trainer.state), devices[0]),
            "params_bytes": device_bytes(jax.tree.leaves(trainer.state.params), devices[0]),
            "reference_bytes_in_use_max": None}
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    slice_ends_s = []  # in `info`: shows where in the window a stall fell
    while not slice_ends_s or slice_ends_s[-1] < seconds:
        run_slice()
        slice_ends_s.append(time.perf_counter() - t_open)
    jax.block_until_ready(trainer.state)
    window_s = time.perf_counter() - t_open
    steps = len(trainer.step_losses) - steps_before
    window_losses = trainer.step_losses[steps_before:]
    if dict(trainer.engine.trace_counts) != traced_before:
        raise NoResult(f"a program compiled inside the measured window: trace_counts "
                       f"{traced_before} -> {dict(trainer.engine.trace_counts)}")
    goodput = None
    if goodput_before is not None:
        goodput = {k: v - goodput_before.get(k, 0.0) for k, v in trainer.goodput.buckets.items()}
    peak_bytes = memory_peak_bytes(devices)
    mem_stats = {k: int(v) for k, v in (devices[0].memory_stats() or {}).items()}
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    failed += int(getattr(trainer, "nonfinite_steps", 0))

    # Free the program before the reference runs: the peak is read, the
    # reference must fit on the chip the program filled.
    del recorder, trainer
    gc.collect()
    jax.clear_caches()
    # The reference runs some hundred small programs (an update a leaf's shape and step, the norms' eager
    # operations), each compiled in a fraction of the second under which jax keeps nothing: every run of
    # every check compiled them anew, 4 s of them in the LM cells. From here on the cache keeps everything.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    def watch():  # the reference's own high-water mark, where the backend counts bytes
        in_use = (devices[0].memory_stats() or {}).get("bytes_in_use")
        if in_use is not None:
            held["reference_bytes_in_use_max"] = max(in_use, held["reference_bytes_in_use_max"] or 0)

    t_ref = time.perf_counter()
    reference = refrun.run_reference(ref, cfg, traffic, weight_seed, prog["batches"], devices=devices, watch=watch)
    numbers = refrun.gaps(prog, reference)
    readings = {"program": numbers}
    for name in ([stand_in] if stand_in else []) + (sorted(STAND_INS) if extra_readings else []):
        kwargs = STAND_INS[name](len(prog["batches"][0]["label"]), cfg, len(devices))
        if name not in readings and kwargs is not None:
            placed = refrun.run_reference(ref, cfg, traffic, weight_seed, prog["batches"], devices=devices,
                                          watch=watch, **kwargs)
            readings[name] = refrun.gaps(placed, reference)
    if stand_in:
        numbers = readings[stand_in]
    ref_s = time.perf_counter() - t_ref
    limits = traffic["limits"][cfg["name"]]
    checks = {k: {"value": numbers[k], "limit": limits[k], "leaf": numbers.get(k + "_leaf")} for k in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and not problems and failed == 0

    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    ctx = {
        "cell": cell, "cfg": cfg, "traffic": traffic, "window_s": window_s, "steps": steps,
        "setup_s": setup_s, "goodput": goodput, "memory_stats": mem_stats, "memory_peak_bytes": peak_bytes,
        "chips": len(devices), "device_kind": dev0.device_kind, "trace": None,
        "trace_steps": traffic.get("trace_slices", 1) * traffic["steps_per_epoch"],
    }
    result = {"correct": bool(correct), "attempted": steps, "failed": failed}
    if trace:
        summary = trace_lib.load(*trace_info)
        ctx["trace"] = summary
        # An unknown TPU is an error; off the chip (tests only) nothing that needs a peak is reported.
        ctx["peaks"] = peaks.peaks_for(dev0.device_kind) if dev0.platform == "tpu" else None
        device["busy_s"], device["window_s"] = summary.busy_s, summary.window_s
        result["metrics"] = read_per_layer(cell, ctx)
        result["breakdown"] = {"device_ops": summary.top_ops(), "idle_gaps": summary.top_gaps()}
        keep = os.environ.get("BENCH_KEEP_TRACE_DESCRIPTION")
        if keep:
            trace_lib.describe(trace_info[0], keep)
    else:
        measured = {
            "step_ms": {"value": 1e3 * window_s / max(steps, 1), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        result["metrics"] = {m["name"]: measured[m["name"]] for m in cell.end_to_end}
    result["device"] = device
    result["info"] = {"window_s": window_s, "slice_ends_s": slice_ends_s, "reference_s": ref_s, "problems": problems,
                      "recorded_steps": len(prog["losses"]), "leaves_left_out": numbers["leaves_left_out"],
                      "memory_stats": mem_stats, "held": held,
                      "setup_stamps_s": stamps}
    if extra_readings:
        result["readings"] = readings
    result["checks"] = checks  # last: each number compared beside its limit
    return result


# The reference put in the program's place: the control (the nearest precision
# below the one the configuration states: fp8 under bfloat16, bfloat16 under
# float32) and the planted faults that need a reading. The exchange between
# chips left out is what one chip's copy holds when it steps on the gradient
# of its own rows alone; a one-chip cell cannot have it (None).
STAND_INS = {
    "control": lambda rows, cfg, chips: {"control": cfg["precision"]["control"]},
    "half_batch": lambda rows, cfg, chips: {"keep_rows": rows // 2},
    "no_exchange": lambda rows, cfg, chips: {"keep_rows": rows // chips} if chips > 1 else None,
}


def read_per_layer(cell, ctx) -> dict:
    """Each per-layer metric is a file of its own, found by name; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        reader = None
        for base in cell.data_dirs:
            path = os.path.join(base, "metrics", m["name"] + ".py")
            if os.path.exists(path):
                spec = importlib.util.spec_from_file_location("bench_metric_" + m["name"].replace(".", "_"), path)
                reader = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(reader)
                break
        if reader is None:
            raise NoResult(f"no reader benchmarks/metrics/{m['name']}.py for per-layer metric {m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def print_result(result: dict) -> None:
    lines = [f"check {k}: {c['value']:.6g} (limit {c['limit']:.6g}"
             + (f", worst leaf {c['leaf']}" if c.get("leaf") else "") + ")"
             for k, c in result["checks"].items()]
    lines.append(f"failed steps: {result['failed']} of {result['attempted']}; "
                 f"path problems: {result['info']['problems'] or 'none'}; correct: {result['correct']}")
    print("\n".join(lines), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
