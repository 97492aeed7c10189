#!/usr/bin/env python
"""chip_smoke.py — the standing proof that the trainer starts on the chip.

One process, run from the root of a copy of the repo (not necessarily a git
checkout, no network), using every chip it finds (1 or 4). It goes through
the normal entry points — the trainer classes ``run.sh`` launches, not a
hand-built step — on data generated from a seed, checks what comes out, and
prints as the LAST line of stdout::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Exit 0 only when every phase ran and every check held. It claims no speed:
every time it prints is labelled informational.

Phases
  kernels  every ``pl.pallas_call`` site in ``ops/pallas.py`` that no default
           path reaches, COMPILED (never interpreted) at the shapes its
           callers use and compared with its plain float32 reference: flash
           non-causal with ``valid_len`` (the ViT ``pad_seq_to`` path), flash
           causal at T=1024, T=4096 and T=8192 (the backward one Mosaic call
           at each), ``flash_block_fwd/bwd`` (the ring path's blocks),
           ``conv1x1_bn_act`` relu/identity at ResNet stage-1 shapes and gelu
           at ConvNeXt-L's expand shapes; and the Mamba-2 scan's two kernels
           (``ops/ssd.py``: ``ssd_fwd`` / ``ssd_bwd``) at granite-4.0-h-micro's
           widths and at nemotron-3-nano-30b-a3b's (eight ``B`` / ``C`` groups,
           chunk 128, T=8192) against the sequential float32 recurrence; the
           causal convolution's two kernels (``ops/causal_conv.py``:
           ``conv_fwd`` / ``conv_bwd``) at both models' widths and cells' T
           against its ``jax.numpy`` form; and the
           routed expert layer's four row movements (``ops/moe_rows.py``) at
           that model's shape against their ``jax.numpy`` form at 3%, 6.25% and
           100% of the pairs held here and at joyai-llm-flash's (131,072
           pairs) at 6.25%, and the whole layer, relu² and SwiGLU, with the
           rows the kernels never write set to NaN. Plus two device
           checks: ``tpu_compiler_options()`` is accepted by the installed
           libtpu, and ``jax.block_until_ready`` really blocks.
  leg_a    ``Cifar10Trainer`` (examples/train_cifar10.py): VGG16 at full
           width, CIFAR-10 shape, bf16, global batch 1024, chained windows,
           ``telemetry="on"``, ``preflight="on"``. One epoch on the synthetic
           set (48 steps, validation, ``best`` + ``last`` saves), then a
           second ``Cifar10Trainer(snapshot_path=<last>)`` that restores on
           the device and trains on — checkpoint and resume are part of the
           main path. Both are configured for the same two epochs (the first
           is stopped after one), so the second compiles the SAME programs
           and its compile time shows the cache at work.
  leg_b    ``LMTrainer`` (examples/train_lm.py): GPT-2-small at its published
           width and depth, T=1024, bf16, fused tied-CE, global batch 64
           (32 on a single chip, where the preflight predicts 64 does not
           fit), attention left on auto — which on the chip must resolve to the
           Pallas flash kernel, forward and backward. On four chips it runs
           on ``data=4`` and on ``data=2 x tensor=2`` through
           ``Trainer(mesh=...)``, and the per-device HLO must show the Mosaic
           call with the per-device batch (and heads). Validation and saves
           are off in this leg: leg A owns checkpointing, and a GPT-2-small
           state is 1.5 GB per save.

Serving is NOT in this smoke: it has no entry point at a real width
(``bench.py`` serves ``LMTiny``, seq 16, vocab 64) and ROADMAP R3 rewrites its
loop around token-level batching.

No fallback hides the device: without a TPU the default invocation fails at
once, naming the platform it found. ``--rehearse-cpu`` is the on-chip guide's
tiny CPU rehearsal (kernels interpreted, toy sizes, optional
``--devices N`` virtual devices) for debugging THIS SCRIPT before spending
chip time: its output is labelled a rehearsal, it prints no ``"ok": true``
line, and it exits 64 when everything ran — never 0. ``--only`` runs a
subset of phases and is likewise never a pass.

The compile cache goes where ``utils.enable_compile_cache`` puts it:
``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import re
import shutil
import sys
import tempfile
import time
import traceback

EXIT_REHEARSAL = 64  # a completed rehearsal / partial run: never a pass
PHASES = ("kernels", "leg_a", "leg_b")
REPO = os.path.dirname(os.path.abspath(__file__))

# Sizes. "chip" is the contract; "rehearsal" only has to reach every line.
# cifar_base_lr: the entry scales it by batch/256, so both give a peak LR of
# 0.01 — VGG16 has no normalisation layers and the entry's default (peak 0.4
# at batch 1024) diverges on the synthetic set within these 96 steps.
# lm_batch: 64 (bench.py's LM batch) wherever it fits. On ONE 16 GB chip the
# repo's own preflight predicts it does not — 14.31 GiB against 14.17 GiB
# usable at batch 64, chained x2 (v5e, PR 21) — so a single chip runs 32.
CHIP = dict(
    cifar_batch=1024, cifar_chain=4, cifar_log_every=12, cifar_base_lr=0.0025,
    lm_size="small", lm_seq=1024, lm_batch=64, lm_batch_one_chip=32, lm_chain=2, lm_epochs=2,
)
REHEARSAL = dict(
    cifar_batch=32, cifar_chain=2, cifar_log_every=2, cifar_base_lr=0.08,
    lm_size="tiny", lm_seq=128, lm_batch=8, lm_batch_one_chip=8, lm_chain=2, lm_epochs=2,
)


class Smoke:
    """Collects checks and informational lines; a failed check never stops
    the run (one chip call should report everything it can)."""

    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        self.failures: list[str] = []
        self.info_rows: dict = {}
        self.t0 = time.perf_counter()

    def say(self, msg: str) -> None:
        print(f"[{time.perf_counter() - self.t0:7.1f}s] {msg}", flush=True)

    def info(self, key: str, value) -> None:
        """Informational, never a result."""
        self.info_rows[key] = value
        self.say(f"info  {key} = {value}")

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        self.say(f"check {name}: {'ok' if ok else 'FAILED'}{(' — ' + detail) if detail else ''}")
        if not ok:
            self.failures.append(f"{name}: {detail}" if detail else name)
        return bool(ok)

    def case(self, name: str, fn, *args, **kwargs) -> None:
        """One case of a table: a raise (a compiler refusal, say) fails this
        case and the run, and the cases after it still report."""
        try:
            fn(name, *args, **kwargs)
        except Exception as e:  # noqa: BLE001 — case boundary: recorded, reported, fails the run
            first = (str(e).strip().splitlines() or [""])[0]
            self.check(name, False, f"raised {type(e).__name__}: {first[:600]}")

    def phase(self, name: str, fn) -> None:
        self.say(f"=== phase {name} ===")
        t = time.perf_counter()
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — phase boundary: recorded, reported, fails the run
            traceback.print_exc()
            self.failures.append(f"phase {name} raised {type(e).__name__}: {str(e)[:400]}")
        self.info(f"{name}.wall_s", round(time.perf_counter() - t, 1))


class FileLogger:
    """The trainer's ``logger=``: everything to a file, warnings and errors
    also to stdout (a swallowed-phase warning must be visible in the tail)."""

    def __init__(self, path: str, smoke: Smoke):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")
        self._smoke = smoke
        self._shown = 0

    def log(self, msg, log_type="info"):
        self._f.write(f"{log_type.upper()}: {msg}\n")
        self._f.flush()
        if log_type != "info":
            self._shown += 1
            if self._shown <= 8:  # the tail of stdout is what comes back
                self._smoke.say(f"trainer {log_type}: {str(msg)[:300]}")
            elif self._shown == 9:
                self._smoke.say("trainer: further warnings go to the run log only")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._f.close()


# ---------------------------------------------------------------------------
# kernel table
# ---------------------------------------------------------------------------


def _rel_err(got, ref) -> float:
    """max|got - ref| / max|ref| in float32 — error relative to the
    reference's own scale, so one bound serves outputs and gradients."""
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))


# Tolerances, with the reason: the kernels take bf16 operands, round the
# probabilities / dS to bf16 before their second matmul and round outputs to
# bf16 (relative step 2^-8 = 3.9e-3); against a float32 "highest" reference on
# the SAME bf16-rounded inputs that is what remains. Measured on the v5e
# (PR 21): outputs <= 3.7e-3, gradients <= 6.3e-3 of the reference's scale. The
# bounds leave ~3x of that and stay far below what a dropped block, a wrong
# mask or an 8-bit computation (2^-4) produces.
TOL_FWD = 1e-2
TOL_GRAD = 2e-2


# The scan's kernels are held to the float32 recurrence by norm (|got - ref| /
# |ref| over a whole tensor, as the benchmark's gaps are): dA is 64 numbers,
# each a sum over every step, and a maximum over so few says little. They
# round Δ ⊙ x, L ⊙ C Bᵀ, B, C and the entering state to bf16 once each, as the
# jax.numpy form does. Measured on the v5e (PR 35, [2, 4096, 64, 64], N 128,
# chunk 256): y 2.3e-3, the five gradients 2.3e-3 to 3.3e-3, the jax.numpy
# form with bf16 operands the same to the third digit. Three times that.
TOL_SSD = 1e-2


def _norm_err(got, ref) -> float:
    import jax.numpy as jnp

    got, ref = jnp.asarray(got, jnp.float32), jnp.asarray(ref, jnp.float32)
    return float(jnp.linalg.norm((got - ref).ravel()) / jnp.maximum(jnp.linalg.norm(ref.ravel()), 1e-30))


def _reference_attention(q, k, v, *, causal=False, valid_len=None):
    """Plain float32 softmax attention on [B, T, H, D] (``highest`` matmul
    precision: a TPU float32 matmul is otherwise a bf16 one). Returns
    ``(o, lse)``; ``lse`` is [B, H, Tq]."""
    import jax
    import jax.numpy as jnp

    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.ones((tq, tk), bool)
        if causal:
            mask = jnp.tril(mask)
        if valid_len is not None:
            mask = mask & (jnp.arange(tk) < valid_len)[None, :]
        s = jnp.where(mask[None, None], s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v)
    return o, lse


def _qkv(seed, shape, dtype):
    import jax

    keys = jax.random.split(jax.random.key(seed), 4)
    return [jax.random.normal(kx, shape, dtype) for kx in keys]


def _compile(fn, *args):
    """Lower and compile once: the executable runs the case AND supplies the
    HLO text its Mosaic check reads."""
    import jax

    return jax.jit(fn).lower(*args).compile()


def _has_mosaic_call(hlo_text: str) -> bool:
    return "tpu_custom_call" in hlo_text


def phase_kernels(smoke: Smoke, devices) -> None:
    import jax
    import jax.numpy as jnp

    from distributed_training_pytorch_tpu.ops import pallas as P
    from distributed_training_pytorch_tpu.utils.tpu import tpu_compiler_options

    on_tpu = devices[0].platform == "tpu"
    dt = jnp.bfloat16
    small = smoke.rehearsal
    # interpret=None everywhere: resolve_interpret compiles on a TPU. On the
    # chip every case below additionally asserts the Mosaic custom call is in
    # the compiled HLO, so an interpreted kernel cannot pass as a compiled one.
    smoke.check(
        "kernels.resolve_interpret", P.resolve_interpret(None) is (not on_tpu),
        f"resolve_interpret(None)={P.resolve_interpret(None)} on {devices[0].platform}",
    )

    def mosaic_check(name, compiled):
        if on_tpu:
            smoke.check(f"{name}.mosaic_in_hlo", _has_mosaic_call(compiled.as_text()))

    def flash_case(name, b, t, h, d, *, causal, valid_len=None, dv=None, ref_heads=None):
        q, k, v, g = _qkv(1, (b, t, h, d), dt)
        if dv is not None:  # values (and the output, and its gradient) at a width of their own
            v, g = v[..., :dv], g[..., :dv]
        rows = valid_len or t
        if valid_len is not None:
            g = g.at[:, valid_len:].set(0)  # the loss ignores pad rows

        def f(q, k, v):
            return P.flash_attention(q, k, v, causal=causal, valid_len=valid_len)

        def f_ref(q, k, v):
            return _reference_attention(q, k, v, causal=causal, valid_len=valid_len)[0]

        t_c = time.perf_counter()
        fwd_bwd = _compile(lambda q, k, v: jax.vjp(f, q, k, v)[1](g) + (f(q, k, v),), q, k, v)
        out = jax.block_until_ready(fwd_bwd(q, k, v))
        smoke.info(f"{name}.compile_and_run_s", round(time.perf_counter() - t_c, 2))
        if ref_heads is not None:  # heads are independent: the float32 reference's [B, H, T, T] scores for a few
            t_r = time.perf_counter()
            for _ in range(3):
                out = jax.block_until_ready(fwd_bwd(q, k, v))
            smoke.info(f"{name}.fwd_bwd_ms", round((time.perf_counter() - t_r) / 3 * 1e3, 2))
            out = tuple(x[:, :, :ref_heads] for x in out)
            q, k, v, g = (x[:, :, :ref_heads] for x in (q, k, v, g))
        got_q, got_k, got_v, o = out
        rq, rk, rv = jax.jit(lambda q, k, v: jax.vjp(f_ref, q, k, v)[1](g.astype(jnp.float32)))(q, k, v)
        ro = jax.jit(f_ref)(q, k, v)
        errs = {
            "o": _rel_err(o[:, :rows], ro[:, :rows]),
            "dq": _rel_err(got_q[:, :rows], rq[:, :rows]),
            "dk": _rel_err(got_k[:, :rows], rk[:, :rows]),
            "dv": _rel_err(got_v[:, :rows], rv[:, :rows]),
        }
        finite = all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32)))) for x in out)
        ok = finite and errs["o"] <= TOL_FWD and max(errs["dq"], errs["dk"], errs["dv"]) <= TOL_GRAD
        smoke.check(name, ok, f"shape {(b, t, h, d)}" + (f", v {dv} wide" if dv else "") + f" rel err {errs}")
        mosaic_check(name, fwd_bwd)
        if on_tpu:  # the backward is ONE Mosaic call (dq, dk, dv together), whatever T
            calls = re.findall(r"%(flash_\w+)\.\d+ = ", fwd_bwd.as_text())
            smoke.check(f"{name}.one_backward_kernel",
                        sorted(set(calls)) == ["flash_dqkv", "flash_fwd"] and calls.count("flash_dqkv") == 1,
                        f"Mosaic calls {calls}")

    # (name, B, T, H, D, causal, valid_len): [0] the chip's shapes, [1] the
    # rehearsal's. ViT-B/16: T=197 padded to 256 by ViT.pad_seq_to, 12 heads
    # of 64. The LM's default path (T=1024: one block pair, walked in static
    # sub-tiles), the benchmark's long cell (T=4096: several block pairs, dq
    # summed over the k-block grid axis) and the long-context shape (T=8192),
    # each at the blocks ops.pallas._flash_blocks gives it: heads cut to 4 and
    # 2 so the float32 reference's [B,H,T,T] scores (0.5 GB) fit beside the
    # kernel's operands.
    for chip, toy in (
        (("flash_vit_valid_len", 8, 256, 12, 64, False, 197),
         ("flash_vit_valid_len", 2, 32, 2, 16, False, 25)),
        (("flash_causal_1024", 2, 1024, 12, 64, True, None),
         ("flash_causal_1024", 1, 64, 2, 16, True, None)),
        (("flash_causal_4096", 2, 4096, 4, 64, True, None),
         ("flash_causal_4096", 1, 128, 2, 16, True, None)),
        (("flash_causal_8192", 1, 8192, 2, 64, True, None),
         ("flash_causal_8192", 1, 256, 2, 16, True, None)),
    ):
        name, b, t, h, d, causal, valid_len = toy if small else chip
        smoke.case(f"kernels.{name}", flash_case, b, t, h, d, causal=causal, valid_len=valid_len)
    # Latent attention's widths (joyaiflash_t8192): queries and keys 192 wide, values 128, at the cell's
    # whole shape; the reference takes heads 0-1 of the same call (2 x 2 x 8192^2 float32 scores).
    b, t, h = (1, 256, 2) if small else (2, 8192, 32)
    smoke.case("kernels.flash_mla", flash_case, b, t, h, 192, causal=True, dv=128, ref_heads=2)

    # flash_block_fwd/bwd — the ring path's per-block passes (a 2048-token
    # resident q shard against one visiting K/V block: 8192 tokens over 4
    # chips). For ONE block the block's own lse and delta are the global
    # ones, so the reference is plain attention's (o, lse) and gradients.
    def block_case(name, b, tl, h, d, *, causal):
        q, k, v, g = _qkv(2, (b, tl, h, d), dt)
        fwd = _compile(lambda q, k, v: P.flash_block_fwd(q, k, v, causal=causal), q, k, v)
        o, lse = jax.block_until_ready(fwd(q, k, v))
        ro, rlse = jax.jit(lambda q, k, v: _reference_attention(q, k, v, causal=causal))(q, k, v)
        delta = jnp.sum(g.astype(jnp.float32) * ro, axis=-1).transpose(0, 2, 1)  # [B,H,T]
        bwd = _compile(
            lambda q, k, v: P.flash_block_bwd(q, k, v, g, rlse, delta, causal=causal), q, k, v)
        grads = jax.block_until_ready(bwd(q, k, v))
        rgrads = jax.jit(lambda q, k, v: jax.vjp(
            lambda q, k, v: _reference_attention(q, k, v, causal=causal)[0], q, k, v
        )[1](g.astype(jnp.float32)))(q, k, v)
        errs = {"o": _rel_err(o, ro), "lse": _rel_err(lse, rlse)}
        errs.update({n: _rel_err(a, r) for n, a, r in zip(("dq", "dk", "dv"), grads, rgrads)})
        ok = (
            errs["o"] <= TOL_FWD and errs["lse"] <= TOL_FWD
            and max(errs["dq"], errs["dk"], errs["dv"]) <= TOL_GRAD
        )
        smoke.check(name, ok, f"shape {(b, tl, h, d)} rel err {errs}")
        mosaic_check(f"{name}.fwd", fwd)
        mosaic_check(f"{name}.bwd", bwd)

    for causal in (False, True):
        b, tl, h, d = (1, 64, 2, 16) if small else (1, 2048, 2, 64)
        smoke.case(f"kernels.flash_block_{'causal' if causal else 'full'}", block_case,
                   b, tl, h, d, causal=causal)

    # conv1x1_bn_act: ResNet stage-1's 56x56 (64<->256) 1x1 convs with the
    # folded-BN relu epilogue and the identity epilogue PallasConv1x1 calls
    # it with; ConvNeXt-L's expand Dense+GELU (dim -> 4*dim) at each stage.
    def conv_case(name, lead, cin, cout, *, act):
        kx, kw, ka, kb = jax.random.split(jax.random.key(3), 4)
        x = jax.random.normal(kx, (*lead, cin), dt)
        w = (jax.random.normal(kw, (cin, cout), jnp.float32) * cin**-0.5).astype(dt)
        a = jax.random.uniform(ka, (cout,), jnp.float32) + 0.5
        bias = jax.random.normal(kb, (cout,), jnp.float32)

        def f(x, w):
            return P.conv1x1_bn_act(x, w, a, bias, relu=False, act=act)

        def f_ref(x, w):
            with jax.default_matmul_precision("highest"):
                y = (x.astype(jnp.float32) @ w.astype(jnp.float32)) * a + bias
            if act == "relu":
                y = jnp.maximum(y, 0.0)
            elif act == "gelu":
                y = jax.nn.gelu(y, approximate=True)
            return y

        kernel = _compile(f, x, w)
        err = _rel_err(jax.block_until_ready(kernel(x, w)), jax.jit(f_ref)(x, w))
        smoke.check(name, err <= TOL_FWD,
                    f"x {(*lead, cin)} -> {cout} act={act} rel err {err:.2e}")
        mosaic_check(name, kernel)

    if small:
        conv_shapes = [("relu_resnet_s1", (2, 8, 8), 16, 32, "relu"),
                       ("gelu_convnext", (2, 8, 8), 16, 64, "gelu")]
    else:
        conv_shapes = [("relu_resnet_s1_expand", (32, 56, 56), 64, 256, "relu"),
                       ("relu_resnet_s1_reduce", (32, 56, 56), 256, 64, "relu"),
                       ("identity_resnet_s1", (32, 56, 56), 64, 256, None)]
        conv_shapes += [(f"gelu_convnext_l_{dim}", (16, hw, hw), dim, 4 * dim, "gelu")
                        for dim, hw in ((192, 56), (384, 28), (768, 14), (1536, 7))]
    for tag, lead, cin, cout, act in conv_shapes:
        smoke.case(f"kernels.conv1x1_{tag}", conv_case, lead, cin, cout, act=act)

    # The Mamba-2 scan (ops/ssd.py) at granite-4.0-h-micro's widths and the
    # benchmark cell's batch: bf16 operands, both kernels compiled, against the
    # sequential float32 recurrence on the same bf16-rounded inputs; forward
    # and the gradient of each of its five inputs under a fixed cotangent.
    def ssd_case(name, b, t, h, p, n, chunk, mesh=None, groups=None):
        import contextlib

        from jax.sharding import NamedSharding, PartitionSpec

        from distributed_training_pytorch_tpu.ops import ssd

        if groups is None:  # B and C [b, t, n], every head's
            from benchmarks.reference.granite_hybrid import ssd_sequential
        else:  # [b, t, groups, n], head i reading group i // (h / groups)
            from benchmarks.reference.nemotron_h import ssd_sequential

        k = jax.random.split(jax.random.key(5), 6)
        x = jax.random.normal(k[0], (b, t, h, p), dt)
        delta = jax.nn.softplus(jax.random.normal(k[1], (b, t, h)) - 4.0)  # Δ of 0.01-0.1: a state lives hundreds of steps
        a = -jax.random.uniform(k[2], (h,), minval=1.0, maxval=16.0)
        bm, cm = ((0.3 * jax.random.normal(k[i], (b, t, n) if groups is None else (b, t, groups, n))).astype(dt) for i in (3, 4))
        g = jax.random.normal(k[5], (b, t, h, p))
        args = (x, delta, a, bm, cm)
        if mesh is not None:  # rows over the chips, as a data-parallel step hands them
            rows, whole = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0])), NamedSharding(mesh, PartitionSpec())
            g, *args = jax.device_put((g, *args), (rows, rows, rows, whole, rows, rows))

        def run(fn):
            def both(g, *args):
                y, vjp = jax.vjp(fn, *args)
                return (y,) + vjp(g)
            return both

        t_c = time.perf_counter()
        with jax.sharding.set_mesh(mesh) if mesh is not None else contextlib.nullcontext():
            kernel = _compile(run(lambda *v: ssd.ssd_scan(*v, chunk=chunk, dtype=dt)), g, *args)
        out = jax.block_until_ready(kernel(g, *args))
        smoke.info(f"{name}.compile_and_run_s", round(time.perf_counter() - t_c, 2))
        want = jax.jit(run(ssd_sequential))(g, *(v.astype(jnp.float32) for v in args))
        errs = {key: _norm_err(got, ref) for key, got, ref in zip(("y", "dx", "ddt", "da", "db", "dc"), out, want)}
        finite = all(bool(jnp.all(jnp.isfinite(v.astype(jnp.float32)))) for v in out)
        smoke.check(name, finite and max(errs.values()) <= TOL_SSD,
                    f"x {(b, t, h, p)} states {n} groups {groups} chunk {chunk} norm err {errs}")
        mosaic_check(name, kernel)
        if on_tpu:  # one body each a call site: the backward is ONE Mosaic call, and no second forward
            calls = re.findall(r"%(ssd_\w+)\.\d+ = ", kernel.as_text())
            smoke.check(f"{name}.one_kernel_each", sorted(calls) == ["ssd_bwd", "ssd_fwd"], f"Mosaic calls {calls}")

    ssd_shape = (1, 256, 8, 64, 128, 128) if small else (2, 4096, 64, 64, 128, 256)
    smoke.case("kernels.ssd_scan", ssd_case, *ssd_shape)
    # nemotron-3-nano-30b-a3b's: eight B / C groups of eight heads, chunk 128, the cell's T and batch
    smoke.case("kernels.ssd_scan_groups", ssd_case, *((1, 256, 16, 64, 128, 128) if small else (2, 8192, 64, 64, 128, 128)),
               groups=2 if small else 8)
    if len(devices) > 1:
        # A Mosaic call has no partitioning rule: under a mesh ssd_scan runs its kernels in shard_map, a
        # chip's rows each (the per-device HLO still holds one call of each), dA summed over the chips.
        from distributed_training_pytorch_tpu.parallel import mesh as mesh_lib

        smoke.case("kernels.ssd_scan_data_mesh", ssd_case, ssd_shape[0] * len(devices), *ssd_shape[1:],
                   mesh=mesh_lib.create_mesh({mesh_lib.DATA_AXIS: len(devices)}, devices=devices))

    # The Mamba-2 mixer's causal convolution with its SiLU (ops/causal_conv.py) at both models' widths and their
    # cells' T: the kernels read xBC out of the whole in_proj output as the mixer hands it; values and the three
    # gradients against the jax.numpy form (ops/ssd.py:causal_conv1d) on the same bf16 inputs, and each form's ms.
    def conv_case(name, b, t, d_inner, c, heads):
        from distributed_training_pytorch_tpu.ops.causal_conv import causal_conv_silu
        from distributed_training_pytorch_tpu.ops.ssd import causal_conv1d

        k = jax.random.split(jax.random.key(7), 4)
        proj = jax.random.normal(k[0], (b, t, d_inner + c + heads), dt)
        w = jax.random.uniform(k[1], (4, c), minval=-0.5, maxval=0.5)
        bias = 0.1 * jax.random.normal(k[2], (c,))
        g = jax.random.normal(k[3], (b, t, c), dt)

        def run(fn):
            def both(g, *args):
                y, vjp = jax.vjp(fn, *args)
                return (y,) + vjp(g)
            return both

        forms = {"kernels": lambda *v: causal_conv_silu(*v, start=d_inner, dtype=dt),
                 "jnp": lambda p, w, b: jax.nn.silu(causal_conv1d(p[..., d_inner:d_inner + c], w, b)).astype(dt)}
        compiled = {f: _compile(run(fn), g, proj, w, bias) for f, fn in forms.items()}
        out = {f: jax.block_until_ready(fn(g, proj, w, bias)) for f, fn in compiled.items()}
        for f, fn in compiled.items():
            t0 = time.perf_counter()
            for _ in range(10):
                jax.block_until_ready(fn(g, proj, w, bias))
            smoke.info(f"{name}.{f}_fwd_bwd_ms", round((time.perf_counter() - t0) * 100, 3))
        errs = {key: _norm_err(got, ref) for key, got, ref in zip(("y", "dx", "dw", "db"), out["kernels"], out["jnp"])}
        finite = all(bool(jnp.all(jnp.isfinite(v.astype(jnp.float32)))) for v in out["kernels"])
        # both round y and dx to bf16 once; the float32 sums differ in order only (PR 39: a bf16 ulp at most)
        smoke.check(name, finite and max(errs.values()) <= 4e-3, f"xBC {(b, t, c)} from channel {d_inner}: norm err {errs}")
        mosaic_check(name, compiled["kernels"])
        if on_tpu:
            calls = re.findall(r"%(conv_\w+)\.\d+ = ", compiled["kernels"].as_text())
            smoke.check(f"{name}.one_kernel_each", sorted(calls) == ["conv_bwd", "conv_fwd"], f"Mosaic calls {calls}")

    smoke.case("kernels.conv_granite", conv_case, *((1, 256, 128, 160, 16) if small else (2, 4096, 4096, 4352, 64)))
    smoke.case("kernels.conv_nemotron", conv_case, *((1, 512, 128, 384, 16) if small else (2, 8192, 4096, 6144, 64)))

    # The routed expert layer's four row movements (parallel/moe.py: dispatch, combine and their transposes) at
    # nemotron-3-nano-30b-a3b's shape and the cell's tokens: the kernels of ops/moe_rows.py against the jax.numpy
    # form, with the share of the pairs held here as the window has it (3%), as an even routing would (6.25%) and at
    # the dropless worst (every pair here); each movement's ms in both forms beside it (informational).
    def moe_rows_case(name, n, k, d, held, published, share):
        from distributed_training_pytorch_tpu.ops import moe_rows
        from distributed_training_pytorch_tpu.parallel import moe

        keys = jax.random.split(jax.random.key(11), 8)
        # a token's k experts differ; each is held here with probability ``share``
        here = jnp.argsort(jax.random.uniform(keys[0], (n, held)), axis=1)[:, :k]
        elsewhere = held + jnp.argsort(jax.random.uniform(keys[1], (n, published - held)), axis=1)[:, :k]
        top = jnp.where(jax.random.uniform(keys[2], (n, k)) < share, here, elsewhere).astype(jnp.int32)
        dest, live, src, sizes = moe.held_rows(top, 0, held)
        n_live, tile = jnp.sum(sizes), moe_rows.rows_tile(n)
        pairs = jax.jit(moe_rows.live_pairs, static_argnums=1)
        route = moe.Route(dest, live, src, n_live, *pairs(live, tile))
        x, rows, d_rows = (jax.random.normal(key, shape, dt) for key, shape in zip(keys[3:6], ((n, d), (n * k, d), (n * k, d))))
        weights, d_out = jax.random.uniform(keys[6], (n, k)), jax.random.normal(keys[7], (n, d))
        movements = {  # (the movement, its arguments, which of its outputs are the buffer's rows)
            "dispatch": (lambda t, route, x: (moe._rows_in(t, x, route),), (x,), (0,)),
            "dispatch_bwd": (lambda t, route, d_rows: moe._rows_in_bwd(t, route, d_rows)[:1], (d_rows,), ()),
            "combine": (lambda t, route, rows, weights: (moe._rows_out(t, rows, weights, route),), (rows, weights), ()),
            "combine_bwd": (lambda t, route, rows, weights, d_out: moe._rows_out_bwd(t, (rows, weights, route), d_out)[:2],
                            (rows, weights, d_out), (0,)),
        }
        is_live = (jnp.arange(n * k) < n_live)[:, None]  # what a row past the live ones holds is no one's: left out of the comparison
        errs, ms, finite = {}, {}, True
        for key, (fn, args, buffers) in movements.items():
            outs = {}
            for form, t in (("gather", None), ("pallas", tile)):
                run = jax.jit(functools.partial(fn, t))
                out = jax.block_until_ready(run(route, *args))
                outs[form] = [jnp.where(is_live, v, 0) if i in buffers else v for i, v in enumerate(out)]
                t0 = time.perf_counter()
                for _ in range(5):
                    out = run(route, *args)
                jax.block_until_ready(out)
                ms[f"{key}.{form}"] = round((time.perf_counter() - t0) / 5 * 1e3, 3)
            finite = finite and all(bool(jnp.all(jnp.isfinite(v.astype(jnp.float32)))) for v in outs["pallas"])
            errs[key] = max(_norm_err(g, w) for g, w in zip(outs["pallas"], outs["gather"], strict=True))
        jax.block_until_ready(pairs(live, tile))
        t0 = time.perf_counter()
        jax.block_until_ready([pairs(live, tile) for _ in range(5)])
        ms["live_pairs"] = round((time.perf_counter() - t0) / 5 * 1e3, 3)
        smoke.info(f"{name}.ms", ms)
        # the same float32 sums in another order, rounded to bf16 once where the jax.numpy form rounds
        smoke.check(name, finite and max(errs.values()) <= 1e-5 + (2**-8 if dt == jnp.bfloat16 else 0),
                    f"x {(n, d)} top-{k}, {held} of {published} held, {int(n_live)} of {n * k} pairs live; norm err {errs}")

    # The whole layer with the rows the kernels never write poisoned: the grouped products, relu² and every
    # transpose between the two movements run over a buffer whose dead rows are NaN, and nothing may reach an
    # output or a gradient (the allocation's own leftovers are what they hold in a training step).
    def moe_poisoned_case(name, n, d, width, held, published, k, activation="relu2"):
        from distributed_training_pytorch_tpu.ops import dispatch, moe_rows
        from distributed_training_pytorch_tpu.parallel.moe import HeldExpertsMlp

        layer = HeldExpertsMlp(width, width, published, 0, held, k, 2.5, dt, "chip_smoke", activation)
        x = jax.random.normal(jax.random.key(3), (1, n, d))
        variables = jax.jit(layer.init)(jax.random.key(4), x)
        cot = jax.random.normal(jax.random.key(5), x.shape)

        def both(v, x):
            return jax.value_and_grad(lambda v, x: jnp.sum(cot * layer.apply(v, x).astype(jnp.float32)), argnums=(0, 1))(v, x)

        clean, backends = moe_rows.rows_from_table, dispatch.MOE_ROWS_BACKENDS

        def poisoned(table, src, n_live, weights=None, dot_with=None, fill=None, *, out_dtype=None, **kw):
            out_dtype = out_dtype or table.dtype
            return clean(table, src, n_live, weights, dot_with, jnp.full((src.shape[0], table.shape[1]), jnp.nan, out_dtype),
                         out_dtype=out_dtype, **kw)

        try:
            dispatch.MOE_ROWS_BACKENDS = ()  # the jax.numpy form
            want = jax.jit(both)(variables, x)
            dispatch.MOE_ROWS_BACKENDS = (devices[0].platform,)
            moe_rows.rows_from_table = poisoned
            got = jax.jit(both)(variables, x)
        finally:
            moe_rows.rows_from_table, dispatch.MOE_ROWS_BACKENDS = clean, backends
        leaves = list(zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True))
        finite = all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g, _ in leaves)
        err = max(_norm_err(g, w) for g, w in leaves if float(jnp.linalg.norm(w.astype(jnp.float32))) > 0)
        smoke.check(name, finite and err <= 2e-2, f"{n} tokens of {d}, {held} of {published} held: finite {finite}, worst norm err "
                                                  f"of loss and gradients against the jax.numpy form {err:.2e}")

    rows_shape = (64, 3, 128, 4, 8) if small else (16384, 6, 2688, 8, 128)
    for share in (0.03, 0.0625, 1.0):
        smoke.case(f"kernels.moe_rows_{round(share * 100)}pct", moe_rows_case, *rows_shape, share=0.4 if small and share < 1 else share)
    smoke.case("kernels.moe_rows_poisoned", moe_poisoned_case, *((64, 128, 128, 4, 8, 3) if small else (16384, 2688, 1856, 8, 128, 6)))
    # joyai-llm-flash's layer as its cell runs it: 16,384 tokens of 2,048, top-8, 16 of 256 held, SwiGLU experts of 768;
    # its 131,072 pairs are where the kernels' scalar tables first had to come a tile at a time
    smoke.case("kernels.moe_rows_joyai_6pct", moe_rows_case, *((64, 4, 128, 4, 16) if small else (16384, 8, 2048, 16, 256)),
               share=0.4 if small else 0.0625)
    smoke.case("kernels.moe_rows_poisoned_joyai", moe_poisoned_case,
               *((64, 128, 128, 4, 16, 4) if small else (16384, 2048, 768, 16, 256, 8)), activation="swiglu")

    # bench.py's per-compile options against the installed libtpu.
    opts = tpu_compiler_options()
    if on_tpu:
        x = jnp.ones((8, 32, 32, 16), dt)
        w = jnp.ones((3, 3, 16, 16), dt)
        conv = lambda x, w: jax.lax.conv_general_dilated(  # noqa: E731
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")).sum()
        compiled = jax.jit(jax.grad(conv, argnums=1)).lower(x, w).compile(compiler_options=opts)
        smoke.check("kernels.tpu_compiler_options_accepted",
                    bool(jnp.all(jnp.isfinite(compiled(x, w).astype(jnp.float32)))), f"{opts}")
    else:
        smoke.info("kernels.tpu_compiler_options", f"{opts} (no TPU: nothing to try)")

    # Does block_until_ready block? Time a long matmul chain three ways. If it
    # did not, the read-back after it would absorb the device time.
    n = 256 if small else 4096
    m = jnp.ones((n, n), dt) * 0.01

    @jax.jit
    def chain(m):
        def body(c, _):
            return (c @ m).astype(dt) * 0.5 + m, None
        return jax.lax.scan(body, m, None, length=8 if small else 64)[0]

    _ = float(jax.block_until_ready(chain(m))[0, 0])  # compile + warm, read-back included
    t0 = time.perf_counter()
    r = chain(m)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(r)
    t_block = time.perf_counter() - t0
    _ = float(r[0, 0])
    t_read = time.perf_counter() - t0
    smoke.info("kernels.block_until_ready_ms", {
        "dispatch_returned": round(t_dispatch * 1e3, 2),
        "after_block_until_ready": round(t_block * 1e3, 2),
        "after_scalar_readback": round(t_read * 1e3, 2),
    })
    if on_tpu:
        smoke.check(
            "kernels.block_until_ready_blocks",
            (t_read - t_block) < 0.2 * t_block and t_block > 2 * t_dispatch,
            "the scalar read-back after block_until_ready must add almost nothing, "
            "and the dispatch must return long before the result is ready",
        )


# ---------------------------------------------------------------------------
# the two legs
# ---------------------------------------------------------------------------


def _out_path(name: str) -> str:
    """A file under ``chiprun_out/`` — what the chip tool brings back."""
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    return os.path.join(out, name)


def _events(save_folder: str) -> list[dict]:
    from distributed_training_pytorch_tpu.telemetry import read_events

    return list(read_events(os.path.join(save_folder, "telemetry", "events.jsonl")))


def _common_trainer_checks(smoke: Smoke, tag: str, trainer, events, devices, *,
                           report_compile: bool = True):
    """What every trainer run must show, by the repo's own records."""
    import jax

    on_tpu = devices[0].platform == "tpu"
    n_dev = len(devices)
    counts = dict(trainer.engine.trace_counts)
    smoke.check(f"{tag}.each_executable_compiled_once",
                bool(counts) and all(v == 1 for v in counts.values()), f"trace_counts={counts}")
    start = min((e["epoch"] for e in events if e["event"] == "compile" and "epoch" in e), default=None)
    late = [e for e in events if e["event"] == "compile" and e.get("kind") != "mfu_probe"
            and e.get("epoch", start) != start]
    smoke.check(f"{tag}.nothing_compiles_after_warmup",
                not late and trainer.run_telemetry.late_compiles == 0, f"late compile events: {late}")
    ends = [e for e in events if e["event"] == "epoch_end"]
    smoke.check(f"{tag}.losses_finite",
                bool(ends) and all(e.get("loss") is not None and e["loss"] == e["loss"]
                                   and abs(e["loss"]) != float("inf") for e in ends),
                f"epoch losses {[e.get('loss') for e in ends]}")
    smoke.check(f"{tag}.no_nonfinite_steps", trainer.nonfinite_steps == 0,
                f"nonfinite_steps={trainer.nonfinite_steps}")
    # The MFU probe (telemetry/run.py:RunTelemetry.probe_flops) nets every exception into
    # a warning; the smoke turns that into a failure.
    probes = [e for e in events if e["event"] == "compile" and e.get("kind") == "mfu_probe"]
    smoke.check(f"{tag}.mfu_probe_produced_flops",
                bool(trainer._flops_per_step and trainer._flops_per_step > 0) and len(probes) == 1,
                f"flops_per_step={trainer._flops_per_step}")
    if on_tpu:
        # The peak lookup for the real device_kind, and a utilisation field
        # that exists only because both the count and the peak are known.
        smoke.check(f"{tag}.peak_flops_known_for_device_kind", trainer._peak_flops is not None,
                    f"device_kind={devices[0].device_kind!r}")
        mfus = [e["mfu"] for e in events if e["event"] in ("window", "epoch_end") and "mfu" in e]
        smoke.check(f"{tag}.utilisation_field_reported",
                    bool(mfus) and all(0.0 < m < 1.0 for m in mfus), f"{len(mfus)} records")
    # Work is spread: state and batch shardings span every device, every
    # chip reports a peak, straggler fields are present on a multi-chip host.
    leaf = jax.tree.leaves(trainer.state.params)[0]
    smoke.check(f"{tag}.params_span_all_devices", len(leaf.sharding.device_set) == n_dev,
                f"{len(leaf.sharding.device_set)} of {n_dev}")
    smoke.check(f"{tag}.batch_spans_all_devices",
                len(trainer.engine._batch_sharding.device_set) == n_dev)
    windows = [e for e in events if e["event"] == "window"]
    smoke.check(f"{tag}.window_events_present", bool(windows), f"{len(windows)} windows")
    if on_tpu:
        from distributed_training_pytorch_tpu.memory import device_memory_stats

        peaks = {d.id: (device_memory_stats(d) or {}).get("peak_bytes_in_use", 0) for d in devices}
        smoke.check(f"{tag}.memory_stats_peak_on_every_chip", all(v > 0 for v in peaks.values()), "")
        smoke.info(f"{tag}.peak_bytes_in_use", peaks)
        smoke.info(f"{tag}.memory_stats_device0", device_memory_stats(devices[0]))
        smoke.check(f"{tag}.window_memory_fields",
                    all("live_bytes" in w and "peak_bytes" in w for w in windows))
        pre = [e for e in events if e["event"] == "memory_preflight"]
        smoke.check(f"{tag}.preflight_ran_with_capacity",
                    bool(pre) and trainer.memory_report is not None
                    and trainer.memory_report.capacity_bytes and trainer.memory_report.fits is True,
                    f"predicted_peak={getattr(trainer.memory_report, 'predicted_peak_bytes', None)} "
                    f"capacity={getattr(trainer.memory_report, 'capacity_bytes', None)}")
    if n_dev > 1:
        smoke.check(f"{tag}.straggler_fields_present",
                    bool(windows) and all("chip_skew_ms" in w and w.get("chips_sampled") == n_dev
                                          for w in windows))
    if report_compile:  # (leg A reports its cold/warm pair itself: goodput rides the checkpoint)
        smoke.info(f"{tag}.compile_bucket_s_informational",
                   round(trainer.goodput.buckets.get("compile", 0.0), 1))
    if windows:
        smoke.info(f"{tag}.last_window_step_ms_informational", round(windows[-1]["step_ms"], 2))


def phase_leg_a(smoke: Smoke, devices, sizes, workdir: str) -> None:
    from distributed_training_pytorch_tpu.data import native
    from examples import train_cifar10
    from examples.train_cifar10 import Cifar10Trainer

    # A failed `make` silently selecting the Python input path is a quiet
    # failure (data/native.py now warns); on this path it is an error.
    smoke.check("leg_a.native_input_path_built", native.available(),
                "" if native.available() else "the C++ crop/flip runtime did not build: "
                "data/native.py's warning above names the failed command")
    smoke.info("leg_a.input_path", "native C++" if native.available() else "Python")

    save = os.path.join(workdir, "cifar")
    data_dir = os.path.join(workdir, "no-such-dir")  # -> the seeded synthetic set
    if smoke.rehearsal:
        # Same entry, same loader; a seeded 256-image set so a CPU finishes.
        full = train_cifar10.load_cifar10

        def tiny(_):
            x, y, tx, ty = full(data_dir)
            return x[:256], y[:256], tx[:64], ty[:64]

        train_cifar10.load_cifar10 = tiny

    def build(max_epoch, snapshot, logger):
        return Cifar10Trainer(
            data_dir=data_dir,
            base_lr=sizes["cifar_base_lr"],
            max_epoch=max_epoch,
            batch_size=sizes["cifar_batch"],
            chain_steps=sizes["cifar_chain"],
            log_every=sizes["cifar_log_every"],
            telemetry="on",
            preflight="on",
            have_validate=True,
            save_best_for=("accuracy", "geq"),
            save_period=5,  # the entry's own: validates (and saves `best`) at epoch 0
            save_folder=save,
            snapshot_path=snapshot,
            progress=False,
            logger=logger,
        )

    # Both trainers are built for the same two epochs, so the LR schedule —
    # traced into the step as constants — and with it every program is the
    # same; the first is simply stopped after its first epoch (what a
    # preempted run and its relaunch look like, minus the signal).
    weights = os.path.join(save, "weights")
    with FileLogger(os.path.join(save, "run1.log"), smoke) as log:
        t0 = time.perf_counter()
        first = build(2, None, log)
        first.max_epoch = 1
        first.train()
        cold_s = time.perf_counter() - t0
        events1 = _events(save)
        steps = int(first.state.step)
        smoke.check("leg_a.steps_taken", steps == len(first.train_dataloader) and steps > 1,
                    f"{steps} optimizer steps")
        smoke.check("leg_a.chained_windows_ran",
                    any(k.startswith("chained_") for k in first.engine.trace_counts),
                    f"{dict(first.engine.trace_counts)}")
        saved = sorted(os.listdir(weights))
        smoke.check("leg_a.best_and_last_saved", {"best", "last"} <= set(saved), f"{saved}")
        # `best` is only ever saved from a validation pass's metrics.
        smoke.check("leg_a.validated_then_saved_best",
                    any(e["event"] == "checkpoint_save" and e.get("reason") == "best" for e in events1))
        _common_trainer_checks(smoke, "leg_a", first, events1, devices, report_compile=False)
        if len(devices) > 1:
            _check_flop_count_is_whole_mesh(smoke, first, devices)
        compile1 = first.goodput.buckets.get("compile", 0.0)
        loss1 = [e for e in events1 if e["event"] == "epoch_end"][-1]["loss"]
        del first

    # Resume: a new trainer restores `last` onto the device and trains on.
    with FileLogger(os.path.join(save, "run2.log"), smoke) as log:
        t0 = time.perf_counter()
        second = build(2, os.path.join(weights, "last"), log)
        smoke.check("leg_a.resumed_from_saved_epoch",
                    second.cur_epoch == 1 and int(second.state.step) == steps,
                    f"cur_epoch={second.cur_epoch} step={int(second.state.step)}")
        carried = second.goodput.buckets.get("compile", 0.0)  # goodput rides the checkpoint
        second.train()
        warm_s = time.perf_counter() - t0
        events2 = _events(save)[len(events1):]
        smoke.check("leg_a.restore_event",
                    any(e["event"] == "checkpoint_restore" and e.get("epoch") == 1 for e in events2))
        smoke.check("leg_a.resumed_run_trained_on", int(second.state.step) == 2 * steps,
                    f"step {int(second.state.step)}")
        _common_trainer_checks(smoke, "leg_a.resumed", second, events2, devices, report_compile=False)
        loss2 = [e for e in events2 if e["event"] == "epoch_end"][-1]["loss"]
        smoke.check("leg_a.loss_lower_at_end_than_at_start", loss2 < loss1,
                    f"epoch-mean loss {loss1:.4f} -> {loss2:.4f}")
        compile2 = second.goodput.buckets.get("compile", 0.0) - carried
    # Same programs, same process family: the second trainer's compiles
    # should come out of the persistent cache.
    smoke.info("leg_a.compile_seconds_cold_then_warm_informational",
               {"first_trainer": round(compile1, 1), "resumed_trainer": round(compile2, 1)})
    smoke.info("leg_a.wall_seconds_informational",
               {"first_trainer": round(cold_s, 1), "resumed_trainer": round(warm_s, 1)})


def _check_flop_count_is_whole_mesh(smoke: Smoke, trainer, devices) -> None:
    """``cost_analysis()`` of a partitioned executable counts one device's
    program; the trainer's utilisation divides by the whole mesh's peak, so
    its FLOP count must be the whole mesh's. The same step lowered for a
    one-device mesh (compiled, never run) is the independent figure."""
    import jax

    from distributed_training_pytorch_tpu.parallel.mesh import create_mesh

    solo = trainer.engine.with_mesh(create_mesh(devices=devices[:1]))
    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), trainer.state)
    solo_flops = solo.step_cost_analysis(abstract, trainer._abstract_batch)["flops"]
    ratio = trainer._flops_per_step / solo_flops
    smoke.check("leg_a.flop_count_is_whole_mesh", 0.9 < ratio < 1.25,
                f"{len(devices)}-device trainer {trainer._flops_per_step:.3e} vs one-device "
                f"lowering {solo_flops:.3e} (ratio {ratio:.3f})")


def _mosaic_operand_shapes(hlo_text: str) -> set:
    """(dim0, dim1) of every 4-D operand or result of a Mosaic custom call
    whose last dim is a head size — the kernels run on [B, H, T, D]."""
    found = set()
    for line in hlo_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        for m in re.finditer(r"\w+\[(\d+),(\d+),(\d+),(\d+)\]", line):
            b, h, _, d = (int(x) for x in m.groups())
            if d in (16, 32, 64, 128):
                found.add((b, h))
    return found


def phase_leg_b(smoke: Smoke, devices, sizes, workdir: str) -> None:
    import jax

    from distributed_training_pytorch_tpu.ops import dispatch
    from distributed_training_pytorch_tpu.parallel.mesh import MeshConfig
    from distributed_training_pytorch_tpu.utils.tpu import enable_fast_rng
    from examples.train_lm import LMTrainer

    enable_fast_rng()  # as the entry's __main__ does
    on_tpu = devices[0].platform == "tpu"
    n_dev = len(devices)
    meshes = [("default", None)]
    if n_dev >= 4 and n_dev % 4 == 0:
        meshes.append((f"data{n_dev // 2}_tensor2", MeshConfig(data=n_dev // 2, tensor=2)))
    heads = {"small": 12, "tiny": 4}[sizes["lm_size"]]
    batch = sizes["lm_batch"] if n_dev >= 4 else sizes["lm_batch_one_chip"]

    for name, cfg in meshes:
        tag = f"leg_b.{name}"
        save = os.path.join(workdir, f"lm_{name}")
        with FileLogger(os.path.join(save, "run.log"), smoke) as log:
            trainer = LMTrainer(
                seq_len=sizes["lm_seq"],
                base_lr=3e-4,
                size=sizes["lm_size"],
                moe_every=0,
                max_epoch=sizes["lm_epochs"],
                batch_size=batch,
                chain_steps=sizes["lm_chain"],
                log_every=sizes["lm_chain"],
                mesh=cfg.build() if cfg is not None else None,
                telemetry="on",
                preflight="on",
                have_validate=False,  # leg A owns validation + checkpointing
                save_period=None,
                save_folder=save,
                snapshot_path=None,
                progress=False,
                logger=log,
            )
            smoke.info(f"{tag}.mesh", dict(trainer.mesh.shape))
            trainer.train()
        events = _events(save)
        steps = int(trainer.state.step)
        smoke.check(f"{tag}.at_least_four_optimizer_steps", steps >= 4, f"{steps} steps")
        _common_trainer_checks(smoke, tag, trainer, events, devices)
        ends = [e["loss"] for e in events if e["event"] == "epoch_end"]
        smoke.check(f"{tag}.loss_lower_at_end_than_at_start", len(ends) >= 2 and ends[-1] < ends[0],
                    f"epoch-mean loss {ends}")
        smoke.info(f"{tag}.flops_per_step", trainer._flops_per_step)
        # Attention was left on auto: on the chip that must be the kernel.
        recs = [r for r in dispatch.records() if r["model"] == "transformer_lm" and r["op"] == "attention"]
        smoke.info(f"{tag}.kernel_dispatch_records", recs)
        if on_tpu:
            smoke.check(f"{tag}.dispatch_record_says_flash",
                        bool(recs) and all(r["path"] == "flash" and r["reason"].startswith("auto")
                                           and r["backward"] == "fused" for r in recs), f"{recs}")
            # The compiled train step the MFU probe already built (memoized:
            # no extra compile). Its per-device HLO must hold the Mosaic call
            # on the per-device batch (and, under `tensor`, per-device heads).
            hlo = trainer.engine.compile_step_probe(trainer.state, trainer._abstract_batch).as_text()
            shapes = _mosaic_operand_shapes(hlo)
            axes = dict(trainer.mesh.shape)
            want = (batch // (axes.get("data", 1) * axes.get("fsdp", 1)),
                    heads // axes.get("tensor", 1))
            smoke.check(f"{tag}.mosaic_call_in_step_hlo", _has_mosaic_call(hlo))
            smoke.check(f"{tag}.mosaic_operands_are_per_device", shapes == {want},
                        f"[B,H] of the custom call's operands {sorted(shapes)}, per-device share {want} "
                        f"of global ({batch}, {heads})")
            with open(_out_path(f"lm_step_{name}_{n_dev}chip.custom_calls.txt"), "w") as f:
                f.write("\n".join(ln for ln in hlo.splitlines() if "tpu_custom_call" in ln))
        else:
            smoke.check(f"{tag}.dispatch_record_says_flash_REHEARSAL",
                        bool(recs) and any(r["path"] == "flash" for r in recs),
                        "rehearsal forces PALLAS=1 so the interpreted kernel is on the path")
        del trainer

    if on_tpu and n_dev > 1:
        # Informational (so a raise here is reported, not failed): what GSPMD
        # does with the bare kernel (no shard_map) under a jit over the mesh
        # — the reason flash_attention wraps it.
        try:
            smoke.info("leg_b.bare_kernel_under_gspmd_informational", _bare_kernel_under_gspmd(n_dev))
        except Exception as e:  # noqa: BLE001 — informational only
            smoke.info("leg_b.bare_kernel_under_gspmd_informational",
                       f"raised {type(e).__name__}: {str(e)[:300]}")


def _bare_kernel_under_gspmd(n_dev: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from distributed_training_pytorch_tpu.ops import pallas as P
    from distributed_training_pytorch_tpu.parallel.mesh import create_mesh

    sh = NamedSharding(create_mesh(), PS("data"))
    q, k, v, _ = _qkv(4, (2 * n_dev, 1024, 12, 64), jnp.bfloat16)
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
    bare = jax.jit(lambda q, k, v: P._flash(q, k, v, True, 1024, 1024, False, None),
                   in_shardings=(sh, sh, sh), out_shardings=sh)
    text = bare.lower(q, k, v).compile().as_text()
    return {"global_batch": 2 * n_dev,
            "custom_call_[B,H]": sorted(_mosaic_operand_shapes(text)),
            "all_gathers": text.count("all-gather(")}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="tiny CPU rehearsal of this script (never a pass; exit 64)")
    parser.add_argument("--devices", type=int, default=1,
                        help="virtual CPU devices for --rehearse-cpu (e.g. 4)")
    parser.add_argument("--only", default=None,
                        help=f"comma list of phases from {PHASES} (never a pass; exit 64)")
    args = parser.parse_args()
    only = tuple(p.strip() for p in args.only.split(",")) if args.only else PHASES
    unknown = [p for p in only if p not in PHASES]
    if unknown:
        parser.error(f"unknown phase(s) {unknown}; choose from {PHASES}")

    if args.rehearse_cpu:
        # Explicit, and before anything touches a backend. PALLAS=1 puts the
        # (interpreted) flash kernel on the LM's path, which auto never does
        # off-TPU.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PALLAS"] = "1"
        if args.devices > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.devices}"
            ).strip()

    import jax

    if args.rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no usable backend: {e}", file=sys.stderr)
        return 2
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse_cpu:
        print(
            f"chip_smoke: found platform {platform!r} ({len(devices)} x "
            f"{devices[0].device_kind!r}), not a TPU — this check only passes on the "
            "chip. (--rehearse-cpu runs a labelled, non-passing CPU rehearsal.)",
            file=sys.stderr,
        )
        return 2

    sys.path.insert(0, REPO)
    try:
        import jaxlib

        from distributed_training_pytorch_tpu.telemetry.mfu import device_peak_flops
        from distributed_training_pytorch_tpu.utils.compile_cache import (
            cache_entry_count,
            enable_compile_cache,
        )
    except ImportError as e:
        print(f"chip_smoke: the repo's package is not importable from {REPO}: {e}",
              file=sys.stderr)
        return 2

    smoke = Smoke(rehearsal=args.rehearse_cpu)
    if smoke.rehearsal:
        smoke.say("REHEARSAL on the CPU — toy sizes, interpreted kernels. NOT a pass, "
                  "and no number below is a device number.")
    cache_dir = enable_compile_cache()  # before the first compile
    device = {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}
    smoke.info("device", device)
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    smoke.info("versions", {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                            "libtpu": libtpu_version})
    smoke.info("compile_cache.dir", cache_dir)
    smoke.info("compile_cache.entries_at_start", cache_entry_count(cache_dir))
    if platform == "tpu":
        peak = device_peak_flops(devices[0])
        smoke.check("peak_flops_table_has_this_device_kind", peak is not None,
                    f"{devices[0].device_kind!r} -> {peak}")

    sizes = REHEARSAL if smoke.rehearsal else CHIP
    workdir = tempfile.mkdtemp(prefix="chip_smoke_run_")  # run artefacts only; never a cache
    try:
        if "kernels" in only:
            smoke.phase("kernels", lambda: phase_kernels(smoke, devices))
        if "leg_a" in only:
            smoke.phase("leg_a", lambda: phase_leg_a(smoke, devices, sizes, workdir))
        if "leg_b" in only:
            smoke.phase("leg_b", lambda: phase_leg_b(smoke, devices, sizes, workdir))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    smoke.info("compile_cache.entries_at_end", cache_entry_count(cache_dir))
    smoke.info("total_wall_s", round(time.perf_counter() - smoke.t0, 1))
    try:
        with open(_out_path(f"chip_smoke_{len(devices)}x{platform}.json"), "w") as f:
            json.dump({"device": device, "failures": smoke.failures, "info": smoke.info_rows,
                       "rehearsal": smoke.rehearsal, "phases": list(only)}, f, indent=1, default=str)
    except OSError as e:
        smoke.say(f"could not write the report under chiprun_out/: {e}")

    if smoke.failures:
        print(f"chip_smoke: FAILED — {len(smoke.failures)} problem(s):", file=sys.stderr)
        for failure in smoke.failures:
            print(f"  - {failure}", file=sys.stderr)
        print(json.dumps({"ok": False, "device": device, "failed": len(smoke.failures)}))
        return 1
    if smoke.rehearsal or only != PHASES:
        smoke.say("every phase that ran held — but this was "
                  + ("a CPU REHEARSAL" if smoke.rehearsal else f"a partial run ({only})")
                  + ": not a pass.")
        return EXIT_REHEARSAL
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
