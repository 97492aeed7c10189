"""Headline benchmark: VGG16 / CIFAR-10-shape training throughput on TPU.

BASELINE.json metric: images/sec/chip (VGG16, CIFAR-10), north star >= 60% MFU.
Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} where
``vs_baseline`` is measured MFU / 0.60 (the north-star MFU target — the
reference publishes no numbers of its own, BASELINE.json).

MFU methodology (standard analytic convention, as in the PaLM paper / the
scaling book): model FLOPs are counted from layer shapes — 2*M*N*K per
conv/GEMM, backward pass = 2x forward — divided by wall time and the chip's
peak bf16 FLOP/s. That nominal count is the headline (it is the work an
eager executor like the torch reference performs); ``mfu_exec`` (HLO
conv/dot recount of what the compiler kept after folding — see
utils/hlo_flops.py and scripts/itemize_flops.py) and ``mfu_xla``
(``cost_analysis()``, executed matmuls + VPU elementwise) are reported
alongside. Timing is the best of ``BENCH_WINDOWS`` measured windows on an
AOT-compiled step (one compile, no retrace), each window ended by
``jax.block_until_ready``.

Defaults (none measured on today's chip — ROADMAP S2; see utils/tpu.py):
hardware-RBG PRNG for the dropout masks, global batch 4096 (on multi-chip
runs raise BENCH_BATCH proportionally — the batch is sharded over the data
axis), and a per-compile scoped-VMEM bump (tpu_compiler_options).

Runs on whatever jax.devices() provides, and stamps no utilisation on a
device whose peak is not in ``telemetry/mfu.PEAK_FLOPS``: an unknown
``device_kind`` (the CPU included) is an error here, not a nominal peak.
The compile cache goes where ``utils.compile_cache.enable_compile_cache``
puts it (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_training_pytorch_tpu import memory as memory_lib
from distributed_training_pytorch_tpu.models import VGG16
from distributed_training_pytorch_tpu.ops import cross_entropy_loss, accuracy
from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib
from distributed_training_pytorch_tpu.telemetry import GoodputMeter
from distributed_training_pytorch_tpu.telemetry import mfu as mfu_lib
from distributed_training_pytorch_tpu.telemetry.provenance import provenance_fields
from distributed_training_pytorch_tpu.train import TrainEngine, make_supervised_loss
from distributed_training_pytorch_tpu.utils import hlo_flops
from distributed_training_pytorch_tpu.utils.tpu import enable_fast_rng, tpu_compiler_options

# Peak-FLOPs table + lookup live in telemetry/mfu.py (ISSUE 4) — one source
# of truth shared with the Trainer's per-window MFU reports; re-exported here
# under the historical bench names.
PEAK_FLOPS = mfu_lib.PEAK_FLOPS
peak_flops = mfu_lib.device_peak_flops


def vgg16_train_flops_per_image(model: VGG16, image_size: int) -> float:
    """Analytic train-step FLOPs per image: 2*M*N*K per conv/FC, backward = 2x
    forward (standard MFU convention; pooling/activations not counted)."""
    fwd = 0.0
    size, in_ch = image_size, 3
    for feats, layers in zip(model.stage_features, model.stage_layers, strict=True):
        for _ in range(layers):
            fwd += 2.0 * 9.0 * in_ch * feats * size * size  # 3x3 conv, same pad
            in_ch = feats
        size //= 2  # 2x2 max-pool
    width = in_ch * 7 * 7  # adaptive avg-pool to 7x7, flattened
    for out in (*model.classifier_widths, model.num_classes):
        fwd += 2.0 * width * out
        width = out
    return 3.0 * fwd  # fwd + bwd(2x fwd)


def vit_train_flops_per_image(model, image_size: int) -> float:
    """Analytic ViT train FLOPs per image (2*M*N*K per GEMM; attention counted
    as the two [T,T] matmuls per head group; backward = 2x forward)."""
    p, dm = model.patch_size, model.hidden_dim
    t = (image_size // p) ** 2 + 1  # patches + cls token
    fwd = 2.0 * (image_size // p) ** 2 * (p * p * 3) * dm  # patch embed conv
    per_layer = (
        2.0 * t * dm * 3 * dm  # qkv
        + 2.0 * 2.0 * t * t * dm  # scores + weighted sum
        + 2.0 * t * dm * dm  # out proj
        + 2.0 * 2.0 * t * dm * model.mlp_dim  # mlp in + out
    )
    fwd += model.depth * per_layer + 2.0 * dm * model.num_classes
    return 3.0 * fwd


def resnet_train_flops_per_image(model, image_size: int) -> float:
    """Analytic bottleneck-ResNet train FLOPs per image (2*HW*K^2*Cin*Cout per
    conv; backward = 2x forward; BN/ReLU/pool not counted)."""
    fwd = 0.0
    size = image_size // 2  # 7x7/2 stem
    fwd += 2.0 * size * size * 49 * 3 * model.width
    size //= 2  # 3x3/2 max-pool
    in_ch = model.width
    for stage, num_blocks in enumerate(model.stage_sizes):
        feats = model.width * (2**stage)
        for block in range(num_blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out_size = size // stride
            fwd += 2.0 * size * size * in_ch * feats  # 1x1 reduce (pre-stride)
            fwd += 2.0 * out_size * out_size * 9 * feats * feats  # 3x3 (strided)
            fwd += 2.0 * out_size * out_size * feats * 4 * feats  # 1x1 expand
            if stride != 1 or in_ch != 4 * feats:  # projection shortcut
                fwd += 2.0 * out_size * out_size * in_ch * 4 * feats
            in_ch, size = 4 * feats, out_size
    fwd += 2.0 * in_ch * model.num_classes
    return 3.0 * fwd


def convnext_train_flops_per_image(model, image_size: int) -> float:
    """Analytic ConvNeXt train FLOPs per image (stem + depthwise 7x7 + the
    dim<->4dim MLP pair per block + 2x2 downsamples; backward = 2x forward)."""
    size = image_size // 4
    fwd = 2.0 * size * size * 16 * 3 * model.dims[0]  # 4x4/4 stem
    for stage, (depth, dim) in enumerate(zip(model.depths, model.dims, strict=True)):
        if stage > 0:
            size //= 2
            fwd += 2.0 * size * size * 4 * model.dims[stage - 1] * dim  # 2x2/2
        per_block = (
            2.0 * size * size * 49 * dim  # depthwise 7x7
            + 2.0 * 2.0 * size * size * dim * 4 * dim  # MLP in + out
        )
        fwd += depth * per_block
    fwd += 2.0 * model.dims[-1] * model.num_classes
    return 3.0 * fwd


def lm_train_flops_per_token(model, seq_len: int) -> float:
    """Analytic causal-LM train FLOPs per token: 6*P_matmul + 12*L*T*d
    attention (the standard 6N + attention convention; backward = 2x fwd
    folded into the 6)."""
    dm, L = model.hidden_dim, model.depth
    p_matmul = L * (4 * dm * dm + 2 * dm * model.mlp_dim) + model.vocab_size * dm
    return 6.0 * p_matmul + 12.0 * L * seq_len * dm


# BENCH_DTYPE (ISSUE 3 satellite): compute dtype of the benched step —
# fp32 | bf16 | fp16, or a comma list ("fp32,bf16,fp16") for a sweep that
# prints ONE json line per dtype. Unset reproduces the historical program
# exactly: model-internal bf16 casts, no precision policy in the engine.
# When set, the model is built with that dtype AND the engine applies the
# matching precision.Policy (fp16 adds dynamic loss scaling), so the timed
# step is the one Trainer(precision=...) runs.
BENCH_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "fp16": jnp.float16}


def _bench_dtype(dtype_name):
    """Model dtype for a BENCH_DTYPE value (None = historical bf16 default)."""
    if dtype_name is None:
        return jnp.bfloat16
    if dtype_name not in BENCH_DTYPES:
        raise SystemExit(
            f"unknown BENCH_DTYPE {dtype_name!r} (choose from {sorted(BENCH_DTYPES)})"
        )
    return BENCH_DTYPES[dtype_name]


def _metric_name(cfg, image_size, dtype_name):
    """The entry's self-describing metric string — ONE implementation for
    the success line and the OOM-net line, so a sweep's structured OOM
    record always joins against its sibling entries' metric strings.
    Metric templates name the historical bf16 dtype; a BENCH_DTYPE override
    renames them."""
    return (
        cfg["metric"].format(size=image_size).replace("bf16", dtype_name or "bf16")
    )


# BENCH_MESH (ISSUE 10 satellite): mesh layout of the benched step — a
# spec like "dp8" / "fsdp4x2" / "tp2x4" / "dp2fsdp2tp2" (grammar:
# parallel.mesh.mesh_config_from_spec; docs/parallelism.md), or a comma
# list for a sweep that prints ONE json line per mesh with `mesh`,
# `mesh_axes`, `batch_replicas`, `per_chip_param_bytes`, and the
# per-replica throughput fields alongside the usual per-chip headline —
# the evidence that fsdp/tensor meshes actually shrink per-chip HBM. Unset
# reproduces the historical 1-D data mesh exactly. A tensor>1 mesh applies parallel.transformer_tp_rules
# (conv models match none of its patterns and take the FSDP fallback).
def _bench_mesh(mesh_spec):
    """Build (and validate) the mesh for a BENCH_MESH value. None = the
    historical default data mesh."""
    if mesh_spec is None:
        return mesh_lib.create_mesh()
    try:
        return mesh_lib.mesh_config_from_spec(mesh_spec).build()
    except ValueError as e:
        raise SystemExit(f"BENCH_MESH: {e}") from e


def _bench_memory(compiled, include_peak=True, predicted=None):
    """Per-step device memory: live/peak bytes from the PJRT allocator where
    the backend exposes them (``memory.live.live_memory_fields`` — the ONE
    memory_stats read shared with trainer telemetry and preflight; TPU has
    it, read after the timed windows so peak covers the real step), else
    XLA's ``bytes accessed`` estimate from the compiled program (CPU smoke
    runs). ``predicted_peak_bytes`` (``compiled.memory_analysis()``, the
    preflight predictor) rides every entry so predicted-vs-measured cannot
    silently drift across rounds.

    ``include_peak=False`` for every sweep run after the first:
    ``peak_bytes`` is a process-lifetime high-water mark with no reset
    (the ``memory.live`` documented caveat), so a later (smaller) dtype's
    peak would silently report the earlier run's — live_bytes stays valid
    per-run."""
    out = memory_lib.live_memory_fields(include_peak=include_peak)
    if not out:
        ba = hlo_flops.bytes_accessed(compiled)
        out = {"hlo_bytes_accessed": int(ba)} if ba else {}
    if predicted is None:  # not already captured by the caller's OOM-net ctx
        predicted = memory_lib.predicted_peak_bytes(compiled)
    if predicted is not None:
        out["predicted_peak_bytes"] = predicted
    return out


# BENCH_PALLAS (ISSUE 17): the unified kernel-policy knob (ops/dispatch.py)
# for the benched model — 1 forces the Pallas hot paths, 0 forces plain,
# unset keeps each model's auto policy (the historical program, bit-exact).
# Parsed by the same pallas_from_env the example entries use; every builder
# receives the resolved tri-state.
def _bench_pallas():
    from distributed_training_pytorch_tpu.ops.dispatch import pallas_from_env

    return pallas_from_env({"PALLAS": os.environ.get("BENCH_PALLAS", "")})


def _build_vgg16(num_classes, image_size, dtype, pallas):
    del image_size
    # Via create_model: VGG16 has no fused-kernel coverage and the factory
    # records that resolution once when the knob is set (ops/dispatch.py).
    from distributed_training_pytorch_tpu.models import create_model

    return create_model("vgg16", num_classes, dtype=dtype, pallas=pallas)


def _build_vit(num_classes, image_size, dtype, pallas):
    del image_size
    from distributed_training_pytorch_tpu.models import ViTB16

    # BENCH_FLASH: unset/auto -> shape-aware adapter; 1 -> force the Pallas
    # kernel at any T; 0 -> plain XLA attention. BENCH_PALLAS overrides it
    # (the unified knob wins over the legacy one, models/vit.py).
    flash_env = os.environ.get("BENCH_FLASH", "auto")
    use_flash = {"auto": None, "1": True, "0": False}[flash_env]
    # BENCH_PAD_SEQ: pad the token stream to this length (0 = off). 256 tiles
    # ViT-B's T=197 onto the 128-lane MXU exactly (models/vit.py pad_seq_to).
    pad_seq = int(os.environ.get("BENCH_PAD_SEQ", "0")) or None
    return ViTB16(
        num_classes=num_classes, dtype=dtype, use_flash=use_flash,
        pad_seq_to=pad_seq, pallas=pallas,
    )


def _build_lm(num_classes, image_size, dtype, pallas):
    from distributed_training_pytorch_tpu.models import GPTSmall

    del num_classes  # byte/GPT-2 vocab is part of the model config
    # image_size = sequence length here; long-context runs stretch max_len
    # with it (the flash kernel auto-routes at T>=512).
    return GPTSmall(dtype=dtype, max_len=max(1024, image_size), pallas=pallas)


def _image_batch(rng, batch, size, num_classes, model):
    del model
    return {
        "image": rng.randn(batch, size, size, 3).astype(np.float32),
        "label": rng.randint(0, num_classes, size=(batch,)).astype(np.int32),
    }


def _token_batch(rng, batch, size, num_classes, model):
    # vocab comes from the built model — one source of truth (a drifted
    # registry constant would silently clamp out-of-range ids under jit)
    del num_classes
    vocab = model.vocab_size
    return {
        "image": rng.randint(0, vocab, size=(batch, size)).astype(np.int32),
        "label": rng.randint(0, vocab, size=(batch, size)).astype(np.int32),
    }


def _image_example(size):
    return jnp.zeros((1, size, size, 3))


def _token_example(size):
    return jnp.zeros((1, size), jnp.int32)


def _supervised_loss(model):
    def criterion(logits, b):
        loss = cross_entropy_loss(logits, b["label"])
        return loss, {"loss": loss, "accuracy": accuracy(logits, b["label"])}

    return make_supervised_loss(model, criterion)


def _lm_fused_loss(model):
    # The training entry's exact loss (one implementation, bench == training).
    from distributed_training_pytorch_tpu.models.transformer_lm import make_fused_lm_loss

    return make_fused_lm_loss(model)


# One source of truth per BENCH_MODEL: builder, flops fn, defaults, metric.
BENCH_MODELS = {
    "vgg16": {
        "build": _build_vgg16,
        "flops": vgg16_train_flops_per_image,
        "batch": 4096,
        "image_size": 32,
        "num_classes": 10,
        "metric": "images/sec/chip (VGG16, CIFAR-10-shape, bf16)",
    },
    "vit": {
        "build": _build_vit,
        "flops": vit_train_flops_per_image,
        # Per-chip batch from an earlier round's sweep (not measured on
        # today's chip): off-optimum batches pushed XLA into rematerializing
        # the [B,12,197,197] attention tensors in backward (.remat fusions);
        # at 96/192 the live-set fit and the recompute disappeared. The
        # cliff_probe below guards the choice. In a DP pod the global batch
        # is 192 x n_chips.
        "batch": 192,
        "image_size": 224,
        "num_classes": 1000,
        "metric": "images/sec/chip (ViT-B/16, ImageNet-shape, bf16)",
    },
    "resnet50": {
        # BENCH_PALLAS_1X1=1: the bandwidth-bound STAGE-1 1x1 convs (56x56
        # maps — BottleneckBlock gates on input spatial >= 56) run the Pallas
        # GEMM kernel (models.resnet.PallasConv1x1) instead of XLA's conv.
        # Not measured on today's chip; an earlier round found the kernel
        # faster alone and the full step slower (a fusion barrier) — the
        # flag exists to repeat that measurement, not as a perf default.
        "build": lambda n, size, dtype, pallas: __import__(
            "distributed_training_pytorch_tpu.models", fromlist=["ResNet50"]
        ).ResNet50(
            num_classes=n, dtype=dtype,
            pallas_1x1=os.environ.get("BENCH_PALLAS_1X1", "0") == "1",
            pallas=pallas,
        ),
        "flops": resnet_train_flops_per_image,
        "batch": 256,
        "image_size": 224,
        "num_classes": 1000,
        "metric": "images/sec/chip (ResNet-50, ImageNet-shape, bf16)",
    },
    "convnext_l": {
        "build": lambda n, size, dtype, pallas: __import__(
            "distributed_training_pytorch_tpu.models", fromlist=["ConvNeXtL"]
        ).ConvNeXtL(num_classes=n, dtype=dtype, pallas=pallas),
        "flops": convnext_train_flops_per_image,
        # accum-4 at microbatch 128 = batch 512, and a model-specific
        # scoped-VMEM value (98304 KiB), both from an earlier round's sweep:
        # not measured on today's chip (ROADMAP S2(b)).
        "batch": 512,
        "image_size": 224,
        "num_classes": 21841,
        # BASELINE config 5 is defined WITH grad accumulation; the timed
        # executable includes the accum microbatch scan (BENCH_ACCUM=1 to
        # measure the plain step).
        "accum_steps": 4,
        "metric": "images/sec/chip (ConvNeXt-L, ImageNet-21k-shape, bf16, accum 4)",
        "compiler_options": lambda: {"xla_tpu_scoped_vmem_limit_kib": "98304"},
    },
    # size = sequence length; throughput unit is tokens (batch*T items/step).
    "lm": {
        "build": _build_lm,
        "flops": lm_train_flops_per_token,
        "batch": 64,
        "image_size": 1024,
        "num_classes": 50257,
        "metric": "tokens/sec/chip (GPT-2-small, T={size}, bf16, fused tied-CE)",
        "unit": "tokens/sec/chip",
        "make_batch": _token_batch,
        "example_input": _token_example,
        "make_loss": _lm_fused_loss,
        "items_per_row": lambda size: size,
    },
}
for _name, _cfg in BENCH_MODELS.items():
    _cfg.setdefault("unit", "images/sec/chip")
    _cfg.setdefault("make_batch", _image_batch)
    _cfg.setdefault("example_input", _image_example)
    _cfg.setdefault("make_loss", _supervised_loss)
    _cfg.setdefault("items_per_row", lambda size: 1)
    # Per-model option sets: an earlier round found the scoped-VMEM bump's
    # sign flips between VGG16 and ResNet-50. Not measured on today's chip
    # (ROADMAP S2(b)).
    _cfg.setdefault(
        "compiler_options", tpu_compiler_options if _name in ("vgg16", "vit", "lm") else dict
    )


def build_bench_setup(model_name: str | None = None, dtype_name: str | None = None,
                      mesh_spec: str | None = None):
    """One source of truth for the executable a ``BENCH_MODEL`` names: build
    the registry model + engine + AOT state + sharded batch + per-model
    compiler options from the same env knobs ``main()`` honors. Used by
    ``main()`` and ``scripts/profile_step.py`` so the profiled program IS the
    timed one.

    ``dtype_name`` is ONE ``BENCH_DTYPE`` value (callers handle the sweep);
    None = the historical program (bf16 model casts, no engine policy).
    ``mesh_spec`` is ONE ``BENCH_MESH`` value; None = the historical 1-D
    data mesh with replicated state."""
    model_name = model_name or os.environ.get("BENCH_MODEL", "vgg16")
    if model_name not in BENCH_MODELS:
        raise SystemExit(
            f"unknown BENCH_MODEL {model_name!r} (choose from {sorted(BENCH_MODELS)})"
        )
    cfg = BENCH_MODELS[model_name]
    batch = int(os.environ.get("BENCH_BATCH", str(cfg["batch"])))
    image_size = int(os.environ.get("BENCH_IMAGE_SIZE", str(cfg["image_size"])))
    # Resolved ONCE here; every consumer (engine, main, run_e2e_records)
    # takes it from the returned dict so the knob cannot drift.
    accum_steps = int(os.environ.get("BENCH_ACCUM", str(cfg.get("accum_steps", 1))))
    mesh = _bench_mesh(mesh_spec)
    replicas = mesh_lib.batch_shard_extent(mesh)
    if batch % replicas:
        knob = (
            f"BENCH_MESH {mesh_spec!r}"
            if mesh_spec is not None
            else f"BENCH_BATCH on the default {replicas}-way data mesh"
        )
        raise SystemExit(
            f"{knob}: batch {batch} is not divisible by the mesh's "
            f"batch-shard extent {replicas} (data x fsdp) — round "
            "BENCH_BATCH or re-plan the mesh"
        )
    # ONE rule-resolution policy with the Trainer (parallel.sharding.
    # default_sharding_rules): the benched program is the trained one.
    from distributed_training_pytorch_tpu.parallel import default_sharding_rules

    sharding_rules = default_sharding_rules(mesh)
    model = cfg["build"](
        cfg["num_classes"], image_size, _bench_dtype(dtype_name), _bench_pallas()
    )
    loss_scale = None
    if dtype_name == "fp16":
        from distributed_training_pytorch_tpu.precision import DynamicScale

        loss_scale = DynamicScale.create()
    engine = TrainEngine(
        cfg["make_loss"](model),
        optax.sgd(0.01, momentum=0.9),
        mesh,
        accum_steps=accum_steps,
        precision=dtype_name,  # None -> inactive fp32 policy (historical)
        loss_scale=loss_scale,
        sharding_rules=sharding_rules,
    )
    state = engine.init_state(
        jax.random.key(0),
        lambda rng: model.init(rng, cfg["example_input"](image_size)),
    )
    rng = np.random.RandomState(0)
    gbatch = engine.shard_batch(
        cfg["make_batch"](rng, batch, image_size, cfg["num_classes"], model)
    )
    return {
        "model_name": model_name,
        "cfg": cfg,
        "batch": batch,
        "image_size": image_size,
        "model": model,
        "engine": engine,
        "state": state,
        "gbatch": gbatch,
        "accum_steps": accum_steps,
        "dtype_name": dtype_name,
        "mesh_spec": mesh_spec,
        "mesh": mesh,
        "compiler_options": cfg["compiler_options"]() or None,
    }


def _time_epochs(trainer, epochs: int, batch: int) -> dict:
    """Shared e2e timing protocol: run ``epochs + 1`` full ``train_epoch``
    passes, discard epoch 0 (compiles), report the best remaining epoch."""
    import time as _time

    n_images = len(trainer.train_dataloader) * batch
    times = []
    for epoch in range(epochs + 1):
        trainer.train_dataloader.set_epoch(epoch)
        t0 = _time.perf_counter()
        trainer.train_epoch(epoch)  # epoch-metric device_get = sync
        times.append(_time.perf_counter() - t0)
    dt = min(times[1:])
    return {"e2e_images_per_sec": n_images / dt, "e2e_epoch_s": dt, "e2e_images": n_images}


def run_e2e_records(
    model_name: str, batch: int, epochs: int, image_size: int,
    num_classes: int = 1000, accum_steps: int = 1,
) -> dict:
    """End-to-end throughput for the at-scale records input path (BASELINE
    configs 3-5): pack synthetic JPEGs into .rec shards, then drive the FULL
    ``ImageNetTrainer.train_epoch`` hot path — RecordFileSource -> threaded
    decode + random-resized-crop/flip/normalize -> ``device_prefetch`` ->
    jitted step — exactly what ``MODEL=resnet50 ./run.sh`` runs with
    ``IMAGENET_RECORDS`` set. Epoch 0 pays compiles and is discarded."""
    import shutil
    import sys
    import tempfile

    import cv2

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from examples.train_imagenet import ImageNetTrainer

    from distributed_training_pytorch_tpu.data.records import write_shards
    from distributed_training_pytorch_tpu.utils import Logger

    tmp = tempfile.mkdtemp(prefix="bench_e2e_rec_")
    steps = int(os.environ.get("BENCH_E2E_STEPS", "8"))
    n = steps * batch
    rng = np.random.RandomState(0)

    def payloads():
        for i in range(n):
            img = (rng.randn(256, 256, 3) * 40 + 110).clip(0, 255).astype(np.uint8)
            ok, buf = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 90])
            assert ok
            yield buf.tobytes(), int(rng.randint(0, num_classes))

    write_shards(os.path.join(tmp, "train"), payloads(), num_shards=4)
    # ImageNetTrainer reads these env knobs; save/restore any caller values.
    saved = {k: os.environ.get(k) for k in ("IMAGENET_RECORDS", "NUM_CLASSES")}
    os.environ["IMAGENET_RECORDS"] = os.path.join(tmp, "train-*.rec")
    os.environ["NUM_CLASSES"] = str(num_classes)
    try:
        trainer = ImageNetTrainer(
            model_name=model_name,
            image_size=image_size,
            base_lr=0.1,
            max_epoch=epochs + 1,
            batch_size=batch,
            have_validate=False,
            save_folder=tmp,
            snapshot_path=None,
            progress=False,
            # The config's own accumulation (convnext_l: 4): batch 512
            # without the microbatch split OOMs on one chip.
            accum_steps=accum_steps,
            logger=Logger("bench-e2e-rec", os.path.join(tmp, "log.log")),
        )
        return _time_epochs(trainer, epochs, batch)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(tmp, ignore_errors=True)


def run_e2e(batch: int, epochs: int, chain_steps: int = 1) -> dict:
    """End-to-end throughput: the FULL ``Trainer.train_epoch`` hot path —
    ShardedLoader -> native C++ crop/flip (uint8) -> ``device_prefetch`` ->
    on-device normalize -> jitted step — on materialized (synthetic-CIFAR)
    data. This is the loop the reference times implicitly by training
    (``trainer/trainer.py:143-156``); the step microbench above excludes the
    input pipeline. Epoch 0 pays compiles and is discarded; the best
    remaining epoch is reported. ``chain_steps > 1`` runs the trainer's
    chained-window mode (windows of that many steps dispatch as one device
    program)."""
    import shutil
    import sys
    import tempfile

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from examples.train_cifar10 import Cifar10Trainer

    from distributed_training_pytorch_tpu.utils import Logger

    tmp = tempfile.mkdtemp(prefix="bench_e2e_")
    trainer = Cifar10Trainer(
        data_dir=os.path.join(tmp, "no-such-dir"),  # -> synthetic CIFAR shape
        base_lr=0.1,
        max_epoch=epochs + 1,
        batch_size=batch,
        have_validate=False,
        save_folder=tmp,
        snapshot_path=None,
        progress=False,
        chain_steps=chain_steps,
        # keep stdout to the ONE json line the driver parses
        logger=Logger("bench-e2e", os.path.join(tmp, "log.log")),
    )
    try:
        return _time_epochs(trainer, epochs, batch)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _time_windows(run_once, state, steps, windows, reduce, meter=None):
    """The one window-timing protocol every measurement uses: warm once, then
    ``windows`` timed windows separated by ``BENCH_WINDOW_GAP_S``, each
    ended by ``jax.block_until_ready`` on the window's metrics (checked to
    block on the v5e by ``chip_smoke.py``). ``run_once(state) -> (state,
    metrics)`` runs one window of ``steps`` steps. Returns the carried state
    and the best (or ``reduce="median"``: median) per-step seconds.

    ``meter`` (a ``telemetry.GoodputMeter``) attributes the deliberate
    inter-window gap sleeps to ``other`` — harness pacing is not productive
    step time; the caller ticks ``productive_step`` after the return."""
    state, m = run_once(state)
    jax.block_until_ready(m)
    per_step = []
    for w in range(windows):
        if w:
            if meter is not None:
                meter.tick("productive_step")
            time.sleep(float(os.environ.get("BENCH_WINDOW_GAP_S", "5")))
            if meter is not None:
                meter.tick("other")
        t0 = time.perf_counter()
        state, m = run_once(state)
        jax.block_until_ready(m)
        per_step.append((time.perf_counter() - t0) / steps)
    dt = float(np.median(per_step)) if reduce == "median" else min(per_step)
    return state, dt


def _run_bench(dtype_name: str | None = None, include_peak: bool = True, ctx=None,
               mesh_spec: str | None = None):
    """One full measurement -> one JSON line; returns False when a requested
    phase failed after the line could still be emitted (BENCH_PROFILE).
    ``ctx`` (a dict) is filled with the entry's identity and predicted peak
    as soon as they are known, so the sweep loop's OOM net (``main``) can
    emit a structured line for an entry that died mid-measurement."""
    enable_fast_rng()
    # Before any measurement: no nominal peak for a device that is not in the
    # table (the CPU included) — a utilisation against a made-up denominator
    # is worse than none, and minutes of measurement must not end in it.
    device_peak = peak_flops(jax.devices()[0])
    if device_peak is None:
        raise SystemExit(
            f"bench: no peak FLOP/s known for device_kind "
            f"{jax.devices()[0].device_kind!r} (platform "
            f"{jax.devices()[0].platform!r}) — add it to telemetry/mfu.PEAK_FLOPS "
            "with its source; utilisation is only reported on a known chip"
        )
    # Goodput accounting for the bench run itself (ISSUE 4 satellite,
    # telemetry/goodput.py — the same meter the Trainer carries through
    # checkpoints): compile vs productive-step vs harness-overhead wall time,
    # emitted as bucket fractions in the JSON line so a sweep shows where a
    # config's wall clock went (ConvNeXt-L pays ~10x VGG's compile bill).
    meter = GoodputMeter()
    meter.start()
    setup = build_bench_setup(dtype_name=dtype_name, mesh_spec=mesh_spec)
    meter.tick("other")  # model build + state init + batch staging
    model_name, cfg = setup["model_name"], setup["cfg"]
    batch, image_size = setup["batch"], setup["image_size"]
    if ctx is not None:
        ctx["metric"] = _metric_name(cfg, image_size, dtype_name)
        ctx["batch"] = batch
        if mesh_spec is not None:
            ctx["mesh"] = mesh_spec
    model, engine, state, gbatch = (
        setup["model"], setup["engine"], setup["state"], setup["gbatch"]
    )
    flops_fn = cfg["flops"]
    steps = int(os.environ.get("BENCH_STEPS", "10"))
    windows = int(os.environ.get("BENCH_WINDOWS", "6"))

    # Compile the engine's own step once (AOT), read XLA's FLOP estimate from
    # it, and run that same executable in the timed loop — one compile total.
    # Per-model compiler options (scoped-VMEM bump where it measures faster).
    #
    # BENCH_CHAIN (default on): the window's `steps` train steps are chained
    # on-device (engine.compile_chained_train_steps) so one dispatch runs the
    # whole window back-to-back, so per-call host dispatch is outside the
    # per-step figure. BENCH_CHAIN=0 restores per-step dispatch for
    # comparison.
    chain = os.environ.get("BENCH_CHAIN", "1") != "0"
    opts = setup["compiler_options"]
    step_flops = flops_fn(model, image_size) * batch * cfg["items_per_row"](image_size)
    if chain:
        # One backend compile total: XLA's FLOP estimate comes from the
        # chained executable itself. cost_analysis counts the scan BODY once
        # (verified on v5e: chained flops == single-step flops exactly), so
        # it already IS the per-step figure.
        compiled = engine.compile_chained_train_steps(
            state, gbatch, steps, compiler_options=opts
        )
        cost = hlo_flops.xla_cost_analysis(compiled)
        xla_step_flops = float(cost.get("flops", 0.0))
        # Guard (ADVICE r3): the per-step figure above relies on XLA counting
        # the scan body ONCE (verified on this version: chained == single-step
        # flops exactly). If a future XLA multiplies by trip count, the
        # chained figure lands ~steps x the analytic count — detect that via
        # the analytic anchor (XLA's own count never exceeds ~1.2x analytic;
        # an excess beyond max(steps/2, 2) can only be trip-count
        # multiplication — the floor of 2 keeps a legitimate ~1.2x ratio from
        # tripping the guard at small BENCH_STEPS) and divide back down
        # rather than silently inflating mfu_xla.
        if steps > 1 and step_flops > 0 and xla_step_flops / step_flops > max(steps / 2, 2):
            # never silent (ADVICE r4): this rewrites a measured number
            print(
                f"bench: trip-count guard fired — cost_analysis {xla_step_flops:.3e} "
                f"~ {xla_step_flops / step_flops:.1f}x analytic; dividing by "
                f"steps={steps} (XLA appears to count the chained scan body "
                "per-trip on this version)",
                file=sys.stderr,
            )
            xla_step_flops /= steps
        run_window = lambda st: compiled(st, gbatch)
    else:
        probe = engine.compile_train_step(state, gbatch, compiler_options=opts)
        cost = hlo_flops.xla_cost_analysis(probe)
        xla_step_flops = float(cost.get("flops", 0.0))

        def run_window(st):
            for _ in range(steps):
                st, metrics = probe(st, gbatch)
            return st, metrics

    meter.tick("compile")  # the AOT compile above (XLA, one per run)
    if ctx is not None:
        # Known before the first dispatch: an entry that OOMs in the timed
        # windows still reports the peak the preflight math predicted for it.
        predicted = memory_lib.predicted_peak_bytes(compiled if chain else probe)
        if predicted is not None:
            ctx["predicted_peak_bytes"] = predicted

    # Warmup, then best of `windows` timed windows (BENCH_REDUCE=median
    # reports the median instead; ROADMAP S1 replaces best-of with median
    # and quartiles).
    reduce = os.environ.get("BENCH_REDUCE", "min")
    state, dt = _time_windows(run_window, state, steps, windows, reduce, meter=meter)
    meter.tick("productive_step")

    # Executed-flops recount from the compiled program — BEFORE the e2e
    # block below may delete the executable (see the mfu comment further
    # down for what the three conventions mean).
    from distributed_training_pytorch_tpu.utils.hlo_flops import executed_matmul_flops

    exec_step_flops = executed_matmul_flops(compiled if chain else probe)
    # Per-step device memory + roofline position (ISSUE 3 satellite): read
    # while the timed executable is alive and AFTER the timed windows, so an
    # allocator peak covers the real step's live set. Arithmetic intensity
    # uses XLA's own executed flops over its bytes-accessed estimate — the
    # pair the bf16/fp32 sweep moves together (docs/performance.md roofline).
    memory = _bench_memory(
        compiled if chain else probe,
        include_peak=include_peak,
        # derived exactly once per entry: the OOM-net ctx captured it right
        # after the AOT compile (same executable, same formula)
        predicted=ctx.get("predicted_peak_bytes") if ctx is not None else None,
    )
    arith_intensity = hlo_flops.arithmetic_intensity(compiled if chain else probe)
    # BENCH_MESH comm fields (ISSUE 11): per-category collective bytes of
    # the TIMED executable via the SAME inventory code path the static
    # audit's comm gate checks (analysis.comm_audit.collective_inventory) —
    # a measured sweep entry and the gate argue about identical numbers.
    # The chained executable is a rolled scan whose body (and so each
    # collective) appears once: a per-step figure by the cost_analysis
    # convention. Read here, while the executable is alive.
    comm_fields = {}
    if setup["mesh_spec"] is not None:
        from distributed_training_pytorch_tpu.analysis.comm_audit import (
            comm_fields as _comm_fields,
        )

        comm_fields = _comm_fields(compiled if chain else probe, setup["mesh"])

    # Host dispatch gap (ISSUE 2 satellite): per-step wall time when every
    # step is dispatched from Python — the regime a Trainer WITHOUT
    # chain_steps pays — minus the chained executable's per-step time
    # (device-resident window). The difference is pure host/dispatch
    # overhead: what Trainer(chain_steps=N) removes from train_epoch. The
    # dispatch loop syncs once per window (like the chained loop), not per
    # step, so the gap measures dispatch latency, not added host syncs.
    # BENCH_DISPATCH_GAP=0 skips the extra single-step compile.
    dispatch = {}
    if chain and os.environ.get("BENCH_DISPATCH_GAP", "1") != "0":
        step_probe = engine.compile_train_step(state, gbatch, compiler_options=opts)
        meter.tick("compile")

        def run_dispatch(st):
            for _ in range(steps):
                st, pm = step_probe(st, gbatch)
            return st, pm

        state, dt_dispatch = _time_windows(
            run_dispatch, state, steps, min(3, windows), reduce, meter=meter
        )
        meter.tick("productive_step")
        dispatch = {
            "step_ms_dispatch": round(dt_dispatch * 1e3, 2),
            "dispatch_gap_ms": round((dt_dispatch - dt) * 1e3, 2),
        }
        del step_probe

    # ViT remat-cliff guard: the default batch 192 was chosen to sit on the
    # good side of XLA's backward-remat threshold, a compiler-heuristic
    # cliff a jax/libtpu upgrade is free to move (and today's jax is not the
    # one it was swept under). Probe: time the SAME chained-executable shape
    # as the main measurement (same steps, same reduction) at a known-cliff
    # batch; if the default batch's per-image step time no longer beats it
    # by the expected margin, the heuristic moved — warn loudly and ship the
    # probe numbers in the JSON, so a regression is a diff, not a silent
    # miss. BENCH_CLIFF_PROBE=0 skips (one extra ~35 s compile).
    # Gated to the calibrated default config: a BENCH_BATCH/BENCH_IMAGE_SIZE
    # override moves the sweep the 224-cliff point came from (and a 384px
    # batch-224 probe would also be a memory hazard).
    cliff_probe = {}
    if (
        model_name == "vit"
        and chain
        and os.environ.get("BENCH_CLIFF_PROBE", "1") != "0"
        and "BENCH_BATCH" not in os.environ
        and "BENCH_IMAGE_SIZE" not in os.environ
    ):
        cliff_batch = int(os.environ.get("BENCH_CLIFF_BATCH", "224"))
        probe_rng = np.random.RandomState(7)
        probe_host = cfg["make_batch"](
            probe_rng, cliff_batch, image_size, cfg["num_classes"], setup["model"]
        )
        probe_gbatch = engine.shard_batch(probe_host)
        probe_exec = engine.compile_chained_train_steps(
            state, probe_gbatch, steps, compiler_options=opts
        )
        meter.tick("compile")
        st, probe_dt = _time_windows(
            lambda s: probe_exec(s, probe_gbatch), state, steps, min(3, windows),
            reduce, meter=meter,
        )
        meter.tick("productive_step")
        del st, probe_exec, probe_gbatch
        per_img_main = dt / batch
        per_img_cliff = probe_dt / cliff_batch
        advantage = per_img_cliff / per_img_main
        cliff_probe = {
            "cliff_batch": cliff_batch,
            "cliff_img_per_s": round(cliff_batch / probe_dt, 2),
            "cliff_advantage": round(advantage, 4),
        }
        if advantage < 1.05:
            print(
                f"bench: ViT remat-cliff guard FIRED — batch {batch} is only "
                f"{advantage:.3f}x faster per image than cliff batch "
                f"{cliff_batch}. XLA's backward-remat threshold likely "
                "moved under a compiler upgrade; re-sweep BENCH_BATCH.",
                file=sys.stderr,
            )
            cliff_probe["cliff_guard_fired"] = True


    # Checkpoint save stall (ISSUE 5 satellite): the hot-loop stall one save
    # of THIS config's real TrainState costs, synchronous vs async. The sync
    # figure is the full serialize+hash+fsync+rename wall the pre-resilience
    # trainer paid in the step loop; the async figure is just the
    # device->host snapshot (resilience.AsyncCheckpointSaver), with the
    # commit's wall time reported separately (it runs on the background
    # thread in real training — the bench waits for it only to measure it).
    # BENCH JSONs track the stall reduction across rounds. BENCH_SAVE_STALL=0
    # skips (writes ~2x the model+optimizer state to local disk).
    save_stall = {}
    if os.environ.get("BENCH_SAVE_STALL", "1") != "0":
        import shutil
        import tempfile

        from distributed_training_pytorch_tpu.checkpoint import CheckpointManager
        from distributed_training_pytorch_tpu.resilience import measure_save_stall

        ckpt_tmp = tempfile.mkdtemp(prefix="bench_save_stall_")
        try:
            with CheckpointManager(ckpt_tmp, async_save=False) as mgr:
                # One shared implementation with the chaos soak's < 25%
                # stall acceptance check (resilience.measure_save_stall);
                # the meter gets the trainer-identical checkpoint /
                # checkpoint_async attribution.
                stall = measure_save_stall(mgr, state, meter=meter)
            save_stall = {
                "save_stall_ms": round(stall["stall_ms"], 3),
                "save_sync_ms": round(stall["sync_ms"], 2),
                "save_commit_ms": round(stall["commit_ms"], 2),
                "save_stall_ratio": round(stall["stall_ratio"], 4),
            }
        finally:
            shutil.rmtree(ckpt_tmp, ignore_errors=True)

    # Device-time attribution + dispatch-gap audit (ISSUE 6 satellite):
    # BENCH_PROFILE=1 traces ONE extra window of the exact timed executable
    # and reports where its device wall went — `device_busy_frac` /
    # `dispatch_gap_frac` (the mfu vs mfu_exec gap's prime suspect) and the
    # per-category attribution dict (profiling.analyze_trace; fractions sum
    # to 1 with `idle`) — next to the MFU family. Env-gated (default off,
    # like the heavier BENCH_* extras) so default runs stay cheap; runs
    # BEFORE the e2e block below frees the executable.
    profile_fields = {}
    if os.environ.get("BENCH_PROFILE", "0") == "1":
        import tempfile

        from distributed_training_pytorch_tpu import profiling as profiling_lib

        prof_dir = os.environ.get("BENCH_PROFILE_DIR") or tempfile.mkdtemp(
            prefix=f"bench_prof_{model_name}_"
        )
        # The whole traced window sits inside the net: a profiler that fails
        # to start/stop (unwritable BENCH_PROFILE_DIR, a foreign profiler
        # session already active → RuntimeError) must not lose the
        # already-measured fields — the entry is still emitted, carrying
        # `profile_error`, and the process exits non-zero (a requested phase
        # that failed is a failed run, not a stderr line).
        try:
            with profiling_lib.trace(prof_dir):
                state, pm = run_window(state)
                _ = float(pm["loss"])
                # Tick INSIDE the trace block: only the real steps' wall is
                # productive — stop_trace's on-disk serialization (can rival
                # the window itself for a multi-MB dump) and the analysis
                # below book to "other" at the next tick.
                meter.tick("productive_step")
            profile_report = profiling_lib.analyze_trace(
                prof_dir,
                steps=steps,
                top_k=5,
                flops_by_op=profiling_lib.flops_index(compiled if chain else probe),
            )
            profile_fields = {
                "device_busy_frac": round(profile_report.device_busy_frac, 4),
                "dispatch_gap_frac": round(profile_report.dispatch_gap_frac, 4),
                "categories": {
                    k: round(v, 4) for k, v in profile_report.categories.items() if v
                },
                "profile_trace": prof_dir,
            }
        except (ValueError, FileNotFoundError, OSError, RuntimeError) as e:
            print(f"bench: BENCH_PROFILE failed ({e})", file=sys.stderr)
            profile_fields = {"profile_error": f"{type(e).__name__}: {e}"[:300]}
        finally:
            meter.tick("other")  # stop_trace serialization + analysis (or the failure path)

    # BENCH_E2E=1: also run the input-pipeline-fed epoch loop and report it
    # next to the device-step number (vgg16, and the records path of
    # configs 3-5).
    # BENCH_TRAINER_LOOP=1 (vgg16): the trainer-loop chained mode — the SAME
    # Trainer.train_epoch path with chain_steps=BENCH_CHAIN_STEPS, measuring
    # whether real training closes the dispatch gap the chained microbench
    # predicts (acceptance: trainer_vs_step within ~5% of 1.0).
    e2e = {}
    trainer_loop = {}
    want_e2e = os.environ.get("BENCH_E2E") == "1"
    want_trainer_loop = (
        os.environ.get("BENCH_TRAINER_LOOP") == "1" and model_name == "vgg16"
    )
    if want_e2e or want_trainer_loop:
        # Free the microbench's device state first: its TrainState + batch +
        # executable would otherwise coexist with the e2e trainer's own
        # (ConvNeXt-L: 2 x ~2.4 GB optimizer states + batch-512 workspaces
        # = ResourceExhausted on one 16 GB chip). dt survives for the ratio.
        del state, gbatch, run_window
        if chain:
            del compiled
        else:
            del probe
        setup.pop("state"), setup.pop("gbatch"), setup.pop("engine")
        import gc

        gc.collect()
    e2e_epochs = int(os.environ.get("BENCH_E2E_EPOCHS", "3"))
    if want_e2e:
        if model_name == "vgg16":
            e2e = run_e2e(batch, epochs=e2e_epochs)
        elif model_name in ("resnet50", "convnext_l", "vit"):
            e2e = run_e2e_records(
                {"vit": "vit_b16"}.get(model_name, model_name),
                batch, e2e_epochs, image_size,
                num_classes=cfg["num_classes"],
                accum_steps=setup["accum_steps"],
            )
        if e2e:
            e2e = {k: round(v, 2) if isinstance(v, float) else v for k, v in e2e.items()}
            e2e["e2e_vs_step"] = round(
                e2e["e2e_images_per_sec"] / (batch * cfg["items_per_row"](image_size) / dt), 4
            )
    if want_trainer_loop:
        # Default 10: must divide the Trainer's log_every default (50) —
        # chained syncs land on window boundaries (ctor-validated).
        chain_steps = int(os.environ.get("BENCH_CHAIN_STEPS", "10"))
        tl = run_e2e(batch, epochs=e2e_epochs, chain_steps=chain_steps)
        trainer_step_ms = batch / tl["e2e_images_per_sec"] * 1e3
        trainer_loop = {
            "trainer_chain_steps": chain_steps,
            "trainer_step_ms": round(trainer_step_ms, 2),
            "trainer_vs_step": round(trainer_step_ms / (dt * 1e3), 4),
        }

    # Close the goodput partition (the e2e epochs above, when enabled, run
    # the full Trainer loop — a separate measurement, booked as harness
    # `other` here). Fractions must sum to 1: same invariant the
    # scripts/telemetry_smoke.py gate enforces for trainer runs.
    meter.stop("other")
    fractions = meter.fractions()
    assert abs(sum(fractions.values()) - 1.0) < 1e-6, fractions
    goodput_fields = {
        "goodput": {k: round(v, 4) for k, v in fractions.items() if v},
        "goodput_wall_s": round(meter.total(), 2),
    }

    n_chips = len(jax.devices())
    items = batch * cfg["items_per_row"](image_size)
    images_per_sec = items / dt
    peak = device_peak * n_chips
    # BENCH_MESH entry fields: the mesh's identity, the measured per-chip
    # param residency (the ZeRO-3 HBM win — shard bytes, not global), and
    # per-replica throughput (telemetry.mfu.throughput_fields: dividing a
    # TP mesh's throughput by raw chip count would misread cooperation as
    # slowdown). predicted_peak_bytes already lands via _bench_memory.
    mesh_fields = {}
    if setup["mesh_spec"] is not None:
        from distributed_training_pytorch_tpu.parallel.sharding import (
            tree_shard_bytes,
        )

        mesh_fields = {
            "mesh": setup["mesh_spec"],
            "mesh_axes": {str(k): int(v) for k, v in setup["mesh"].shape.items()},
            "per_chip_param_bytes": int(tree_shard_bytes(state.params)),
            **comm_fields,  # per-category collective bytes (ISSUE 11)
            **{
                k: round(v, 2) if isinstance(v, float) else v
                for k, v in mfu_lib.throughput_fields(
                    images_per_sec, setup["mesh"]
                ).items()
            },
        }
    # Three FLOP conventions, all reported (scripts/itemize_flops.py):
    #   mfu      — nominal layer-formula count: the work an eager executor
    #              (the torch reference) performs for this model. Headline,
    #              comparable across rounds and to reference-style execution.
    #   mfu_exec — executed MXU flops summed over the optimized HLO's
    #              conv/dot instructions (utils.hlo_flops): what the compiler
    #              kept after folding (VGG16/32px: the replicated-pool
    #              classifier folds 25088->512-wide, executed = 0.70x
    #              nominal). None (omitted) where the HLO convention doesn't
    #              reconcile — see executed_matmul_flops's guard.
    #   mfu_xla  — cost_analysis(): executed matmuls + VPU elementwise.
    # (exec_step_flops computed above, before the e2e block frees the
    # executable.)
    # Grad-accumulation scan: XLA's cost_analysis (and the HLO walk) may
    # count the microbatch scan BODY once, undercounting by ~accum (observed
    # exactly 4x at accum 4 / batch 512; at batch 128 XLA unrolled the scan
    # and counted fully). Pick whichever hypothesis — counted-once vs
    # counted-fully — lands the ratio nearer 1x of the analytic anchor in
    # log space; a plain threshold misfires at accum 2 where a fully-counted
    # ~0.85x ratio sits inside any fixed band.
    accum = setup["accum_steps"]
    if accum > 1:
        import math

        def _rescale(flops):
            if not flops:
                return flops
            ratio = flops / step_flops
            if abs(math.log(ratio * accum)) < abs(math.log(ratio)):
                print(
                    f"bench: accum rescale fired — counted {flops:.3e} is "
                    f"{ratio:.2f}x analytic; multiplying by accum={accum} "
                    "(XLA counted the microbatch scan body once)",
                    file=sys.stderr,
                )
                return flops * accum
            return flops

        xla_step_flops = _rescale(xla_step_flops)
        exec_step_flops = _rescale(exec_step_flops)
    # MFU assembly via telemetry/mfu.py — the same flops/dt/peak ratio the
    # Trainer's per-window telemetry reports (one implementation, ISSUE 4).
    mfu = mfu_lib.mfu_value(step_flops, dt, peak) or 0.0
    mfu_exec = mfu_lib.mfu_value(exec_step_flops or 0.0, dt, peak)
    mfu_xla = mfu_lib.mfu_value(xla_step_flops, dt, peak) or 0.0

    # Provenance stamp (ISSUE 14): git SHA + jax/jaxlib + effective
    # XLA_FLAGS + the program identity — without it, a bench line is not
    # attributable and run_compare/bench_history cannot tell two configs
    # apart (four flat rounds went undiagnosed partly for this reason).
    provenance = provenance_fields(
        mesh=setup["mesh_spec"],
        dtype=setup["dtype_name"] or "bf16",
        chain_steps=steps if chain else 1,
        batch=batch,
    )

    print(
        json.dumps(
            {
                "metric": _metric_name(cfg, image_size, setup["dtype_name"]),
                "value": round(images_per_sec / n_chips, 2),
                "unit": cfg["unit"],
                "vs_baseline": round(mfu / 0.60, 4),
                "mfu": round(mfu, 4),
                **({"mfu_exec": round(mfu_exec, 4)} if mfu_exec is not None else {}),
                "mfu_xla": round(mfu_xla, 4),
                # LM convention note: cost_analysis assigns the Pallas flash
                # custom-call 0 FLOPs and counts the fused tied-CE
                # vocab-chunk scan body once, so mfu_xla structurally reads
                # below mfu on this config — an accounting convention, not
                # perf (utils/hlo_flops.py).
                # The tied-CE vocab-scan undercount applies to every LM run;
                # the flash custom-call exclusion only once the auto-route
                # picks the kernel (T >= 512 — below that attention runs
                # plain and cost_analysis DOES count its matmuls).
                **(
                    {
                        "mfu_xla_note": (
                            "excludes flash custom-call + tied-CE scan trips; see utils/hlo_flops.py"
                            if image_size >= 512
                            else "counts tied-CE vocab scan body once; see utils/hlo_flops.py"
                        )
                    }
                    if model_name == "lm"
                    else {}
                ),
                "batch": batch,
                "step_ms": round(dt * 1e3, 2),
                # Compute dtype of the benched step: explicit BENCH_DTYPE, or
                # the historical model-internal-bf16 program when unset.
                "dtype": setup["dtype_name"] or "bf16",
                **mesh_fields,
                **memory,
                **(
                    {"arith_intensity": round(arith_intensity, 2)}
                    if arith_intensity
                    else {}
                ),
                **dispatch,
                **cliff_probe,
                **save_stall,
                **profile_fields,
                **goodput_fields,
                **e2e,
                **trainer_loop,
                "provenance": provenance,
            }
        )
    )
    return "profile_error" not in profile_fields


def _bench_serving():
    """BENCH_SERVE=1 (ISSUE 18 satellite 5): the serving-path headline —
    ``serve_p50_ms`` / ``serve_p99_ms`` / ``serve_qps_per_chip``, one JSON
    line each, provenance-stamped like every training headline. Measures the
    FULL request path (HTTP + admission + micro-batching + compiled forward)
    of an LMTiny replica on a ``tp2`` mesh under saturating closed-loop
    clients, so a regression in any serving layer moves the number.

    Knobs: ``BENCH_SERVE_S`` (measure wall, default 5s), ``BENCH_SERVE_CLIENTS``
    (concurrent closed-loop clients, default 8).
    """
    import json as _json
    import threading
    import urllib.request

    from distributed_training_pytorch_tpu.models import LMTiny
    from distributed_training_pytorch_tpu.serving import (
        InferEngine,
        InferenceServer,
        MicroBatcher,
    )

    seq_len, vocab = 16, 64
    duration_s = float(os.environ.get("BENCH_SERVE_S", "5"))
    n_clients = int(os.environ.get("BENCH_SERVE_CLIENTS", "8"))
    # TP-sharded when the host has 2+ chips; single-chip hosts serve dp1.
    mesh_spec = "tp2" if len(jax.devices()) >= 2 else "dp1"
    devices = jax.devices()[: 2 if mesh_spec == "tp2" else 1]
    mesh = mesh_lib.mesh_config_from_spec(mesh_spec).build(devices)
    model = LMTiny(vocab_size=vocab)
    params = model.init(jax.random.key(0), jnp.zeros((1, seq_len), jnp.int32))[
        "params"
    ]
    engine = InferEngine(
        lambda p, tokens: model.apply({"params": p}, tokens), mesh,
        buckets=(1, 2, 4, 8),
    )
    engine.swap_params(params, version="bench")
    engine.warmup(np.zeros((seq_len,), np.int32))

    server = InferenceServer(
        engine,
        batcher=MicroBatcher(buckets=engine.buckets, max_delay_s=0.004),
        window_s=duration_s + 60.0,
        input_dtype="int32",
        process_index=0,
    ).start()
    stop = threading.Event()
    counts = [0] * n_clients
    errors: list = [None] * n_clients  # a client that raises is a failed run
    try:
        def client(i: int) -> None:
            rng = np.random.default_rng(i)
            url = f"http://127.0.0.1:{server.port}/predict"
            try:
                while not stop.is_set():
                    row = rng.integers(0, vocab, size=(seq_len,)).tolist()
                    body = _json.dumps({"tenant": f"c{i}", "inputs": [row]}).encode()
                    req = urllib.request.Request(
                        url, data=body, headers={"Content-Type": "application/json"}
                    )
                    with urllib.request.urlopen(req, timeout=30.0) as resp:
                        resp.read()
                    counts[i] += 1
            except Exception as e:  # noqa: BLE001 — thread boundary: recorded, re-raised below
                errors[i] = e

        threads = [
            threading.Thread(target=client, args=(i,), daemon=True)
            for i in range(n_clients)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
        stuck = [t.name for t in threads if t.is_alive()]
        failed_clients = [(i, e) for i, e in enumerate(errors) if e is not None]
        if stuck or failed_clients:
            raise SystemExit(
                f"bench: serving clients failed — raised: "
                f"{[(i, repr(e)) for i, e in failed_clients]}, still running "
                f"after join: {stuck}; a client that stops counting would "
                "otherwise read as a slower server"
            )
        elapsed = time.monotonic() - t0
        win = server.window.snapshot()
        qps_per_chip = sum(counts) / elapsed / len(devices)
    finally:
        server.close()

    provenance = provenance_fields(
        mesh=mesh_spec, dtype="float32", chain_steps=1, batch=max(engine.buckets)
    )
    common = {
        "model": "lm_tiny",
        "clients": n_clients,
        "requests": sum(counts),
        "buckets": list(engine.buckets),
        "provenance": provenance,
    }
    for metric, value, unit in (
        ("serve_p50_ms", round(win["p50_ms"], 2), "ms"),
        ("serve_p99_ms", round(win["p99_ms"], 2), "ms"),
        ("serve_qps_per_chip", round(qps_per_chip, 2), "req/s/chip"),
    ):
        print(json.dumps({"metric": metric, "value": value, "unit": unit, **common}))


def main():
    from distributed_training_pytorch_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # before the first compile
    # BENCH_SERVE=1: the serving-path headline instead of the training-step
    # measurement — a separate program (forward-only, latency-bound), so the
    # two benches never contaminate each other's allocator high-water marks.
    if os.environ.get("BENCH_SERVE", "") not in ("", "0"):
        _bench_serving()
        return
    # BENCH_DTYPE sweep: a comma list runs the whole measurement once per
    # dtype (one json line each);
    # a single value (or unset) keeps the one-line contract. Every entry is
    # validated BEFORE the first run — a typo in the last entry must fail in
    # milliseconds, not after the earlier entries' multi-minute measurements.
    sweep = [d.strip() for d in os.environ.get("BENCH_DTYPE", "").split(",") if d.strip()]
    for dtype_name in sweep:
        _bench_dtype(dtype_name)
    # BENCH_MESH sweep (ISSUE 10): one json line per mesh layout; composes
    # with the dtype sweep as an outer product (meshes outermost, so a
    # mesh sweep groups each mesh's dtype lines together).
    # Validated up front like the dtype list — a typo'd last mesh must fail
    # in milliseconds, not after the earlier meshes' measurements.
    mesh_sweep = [
        m.strip() for m in os.environ.get("BENCH_MESH", "").split(",") if m.strip()
    ]
    for spec in mesh_sweep:
        _bench_mesh(spec)
    entries = [
        (mesh_spec, dtype_name)
        for mesh_spec in (mesh_sweep or [None])
        for dtype_name in (sweep or [None])
    ]
    failed = False
    for i, (mesh_spec, dtype_name) in enumerate(entries):
        # peak_bytes only on the first run of the process: the allocator's
        # peak is a lifetime high-water mark (see _bench_memory).
        #
        # OOM net (ISSUE 8 satellite): one oversized dtype/model entry must
        # not abort every entry after it — a RESOURCE_EXHAUSTED entry emits
        # a structured {"oom": true} line (with the peak the memory
        # preflight predicted for it, captured before the first dispatch)
        # and the sweep moves on. Any other failure still aborts: a crash
        # that is not an OOM is a bug, not a fit boundary.
        ctx = {}
        try:
            if not _run_bench(
                dtype_name, include_peak=(i == 0), ctx=ctx, mesh_spec=mesh_spec
            ):
                failed = True
        except Exception as e:  # noqa: BLE001 — classified below, re-raised if not OOM
            if not memory_lib.is_oom_error(e):
                raise
            failed = True
            print(
                json.dumps(
                    {
                        "metric": ctx.get(
                            "metric", os.environ.get("BENCH_MODEL", "vgg16")
                        ),
                        "dtype": dtype_name or "bf16",
                        **({"mesh": mesh_spec} if mesh_spec else {}),
                        "oom": True,
                        **(
                            {"batch": ctx["batch"]} if "batch" in ctx else {}
                        ),
                        **(
                            {"predicted_peak_bytes": ctx["predicted_peak_bytes"]}
                            if "predicted_peak_bytes" in ctx
                            else {}
                        ),
                        "error": (str(e).splitlines() or [type(e).__name__])[0][:300],
                        "provenance": provenance_fields(
                            mesh=mesh_spec,
                            dtype=dtype_name or "bf16",
                            batch=ctx.get("batch"),
                        ),
                    }
                )
            )
            print(
                f"bench: {dtype_name or 'bf16'} entry OOMed — structured line "
                "emitted, continuing the sweep",
                file=sys.stderr,
            )
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
