"""Offline LM evaluation + sampling — the ``eval.py`` analog for the causal-LM
family (beyond the reference's vision-only scope).

Loads a ``train_lm.py`` checkpoint, reports byte-level validation NLL /
perplexity over a corpus, and prints greedy + sampled continuations of a
prompt through the KV-cache decode path (``models.transformer_lm.generate``).

Usage::

    python examples/eval_lm.py [checkpoint_dir] [corpus_file]

Env knobs: ``SEQ_LEN`` (must match training, default 256), ``LM_SIZE``
(``tiny`` | ``small``), ``EVAL_BATCH`` (default 64), ``PROMPT`` (text to
continue; default a corpus prefix), ``GEN_STEPS`` (default 64),
``TEMPERATURE`` (default 0.8; 0 = greedy only).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from distributed_training_pytorch_tpu.checkpoint import CheckpointManager
from distributed_training_pytorch_tpu.models import GPTSmall, LMTiny
from distributed_training_pytorch_tpu.models.transformer_lm import generate
from distributed_training_pytorch_tpu.train import TrainState
from distributed_training_pytorch_tpu.utils import enable_compile_cache


def build_model(size: str, seq_len: int, moe_every: int = 0):
    factory = {"tiny": LMTiny, "small": GPTSmall}[size]
    return factory(
        vocab_size=256, dtype=jnp.bfloat16, max_len=max(seq_len, 128), moe_every=moe_every
    )


def load_params(checkpoint_dir: str, size: str, seq_len: int, moe_every: int = 0):
    """(model, params) from a train_lm checkpoint — shared by evaluate/sample.
    ``moe_every`` must match the training run (the param tree differs)."""
    model = build_model(size, seq_len, moe_every)
    abstract = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, seq_len), jnp.int32)), jax.random.key(0)
    )
    target = TrainState(
        step=jnp.zeros((), jnp.int32),
        params=jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), abstract["params"]),
        opt_state=(),
        model_state={},
        rng=jax.random.key(0),
    )
    mgr = CheckpointManager(os.path.dirname(checkpoint_dir) or ".", async_save=False)
    state, _ = mgr.restore(checkpoint_dir, target, params_only=True)
    mgr.close()
    return model, state.params


def evaluate(checkpoint_dir: str, corpus: str, *, size="small", seq_len=256, batch=64,
             moe_every=0, loaded=None):
    """Returns {"nll": mean byte NLL, "ppl": perplexity, "n_windows": N}."""
    from examples.train_lm import load_windows

    windows = load_windows(seq_len, path=corpus)
    model, params = loaded or load_params(checkpoint_dir, size, seq_len, moe_every)

    @jax.jit
    def batch_nll(params, toks):
        logits = model.apply({"params": params}, toks[:, :-1])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, toks[:, 1:, None], axis=-1)[..., 0]
        return jnp.sum(nll), nll.size

    total, count, n_windows = 0.0, 0, 0
    # Full batches, then the tail (each batch size compiles once; the tail
    # adds at most one extra compile). Dropping the tail silently — or an
    # empty corpus scoring nll=0 — would fabricate results.
    for i in range(0, len(windows), batch):
        chunk = windows[i : i + batch]
        s, n = batch_nll(params, jnp.asarray(chunk))
        total += float(s)
        count += int(n)
        n_windows += len(chunk)
    if count == 0:
        raise ValueError(f"no evaluation windows (corpus too short for SEQ_LEN={seq_len})")
    nll = total / count
    return {"nll": nll, "ppl": float(np.exp(nll)), "n_windows": n_windows}


def sample(checkpoint_dir: str, prompt_text: bytes, *, size="small", seq_len=256,
           gen_steps=64, temperature=0.8, moe_every=0, loaded=None,
           timings: dict | None = None):
    model, params = loaded or load_params(checkpoint_dir, size, seq_len, moe_every)
    prompt = jnp.asarray(np.frombuffer(prompt_text, np.uint8)[None, :], jnp.int32)
    out = {}
    variables = {"params": params}
    key0 = jax.random.key(0)
    greedy = np.asarray(
        generate(model, variables, prompt, gen_steps, key0)
    )  # first call pays the decode-path compile
    if timings is not None:
        import time as _time

        # The np.asarray above already forced the warm-up to completion, so
        # the window below times only the second generate call.
        t0 = _time.perf_counter()
        greedy = np.asarray(generate(model, variables, prompt, gen_steps, key0))
        dt = _time.perf_counter() - t0
        # The scan runs p-1 prompt-prefill steps PLUS gen_steps generation
        # steps, all single-token cached decodes — count them all.
        decode_steps = prompt.shape[1] - 1 + gen_steps
        timings["decode_tok_per_s"] = decode_steps / dt
        timings["decode_steps"] = decode_steps
    out["greedy"] = bytes(greedy[0].astype(np.uint8))
    if temperature > 0:
        out[f"t={temperature}"] = bytes(
            np.asarray(generate(model, variables, prompt, gen_steps,
                                jax.random.key(1), temperature=temperature))[0].astype(np.uint8)
        )
    return out


def decode_benchmark(model, params, *, prompt_len=32, gen_steps=128,
                     batches=(1, 8, 32, 128)) -> list[dict]:
    """Batched KV-cache decode throughput: time greedy
    ``generate`` at several decode batch sizes and report aggregate tok/s and
    per-stream rate. One compile per batch size (shape change); the timed
    window is the second call. Single-token decode is HBM-bandwidth-bound
    (every step streams the full param set), so aggregate tok/s should rise
    nearly linearly with batch until the cache/weights traffic saturates —
    this measures where, instead of claiming it."""
    import time as _time

    variables = {"params": params}
    rows = []
    base = jnp.arange(prompt_len, dtype=jnp.int32)[None, :] % 200 + 32
    for b in batches:
        prompt = jnp.broadcast_to(base, (b, prompt_len))
        key = jax.random.key(0)
        np.asarray(generate(model, variables, prompt, gen_steps, key))  # compile+warm
        t0 = _time.perf_counter()
        np.asarray(generate(model, variables, prompt, gen_steps, key))
        dt = _time.perf_counter() - t0
        steps = prompt_len - 1 + gen_steps  # prefill + generation, all cached
        rows.append({
            "batch": b,
            "tok_per_s": b * steps / dt,
            "tok_per_s_per_stream": steps / dt,
            "step_ms": dt / steps * 1e3,
        })
    return rows


if __name__ == "__main__":
    enable_compile_cache()  # before the first compile (utils/compile_cache.py)
    ckpt = sys.argv[1] if len(sys.argv) > 1 else "./runs/lm/weights/last"
    corpus = sys.argv[2] if len(sys.argv) > 2 else os.environ.get("LM_CORPUS", "")
    size = os.environ.get("LM_SIZE", "small")
    seq_len = int(os.environ.get("SEQ_LEN", "256"))
    moe_every = int(os.environ.get("MOE_EVERY", "0"))  # must match training
    loaded = load_params(ckpt, size, seq_len, moe_every)  # restore once
    if corpus:
        results = evaluate(ckpt, corpus, size=size, seq_len=seq_len,
                           batch=int(os.environ.get("EVAL_BATCH", "64")), loaded=loaded)
        print(f"VALIDATION: nll={results['nll']:.4f} ppl={results['ppl']:.2f} "
              f"({results['n_windows']} windows)")
    # Generation runs for dense AND MoE checkpoints (the MoE decode path
    # is capacity-free and parity-tested).
    prompt = os.environ.get("PROMPT", "").encode() or b"the "
    timings: dict = {}
    for name, text in sample(
        ckpt, prompt, size=size, seq_len=seq_len,
        gen_steps=int(os.environ.get("GEN_STEPS", "64")),
        temperature=float(os.environ.get("TEMPERATURE", "0.8")), loaded=loaded,
        timings=timings,
    ).items():
        print(f"--- {name} ---")
        print(text.decode("utf-8", errors="replace"))
    if timings:
        # Sequential KV-cache decode rate, batch 1, compile excluded
        # (serving throughput scales with decode batch; this is the
        # latency-floor number).
        print(f"DECODE: {timings['decode_tok_per_s']:.1f} tok/s "
              f"(greedy, batch 1, {timings['decode_steps']} single-token steps)")
    # DECODE_BATCHES="1,8,32,128": measure batched decode throughput instead
    # of claiming it scales. DECODE_GEN_STEPS sets
    # the timing window independently of the sampling GEN_STEPS — the
    # per-step rate is window-length sensitive (dispatch amortization), so
    # table rows must come from a fixed window.
    if os.environ.get("DECODE_BATCHES"):
        batches = tuple(int(x) for x in os.environ["DECODE_BATCHES"].split(","))
        model, params = loaded
        for row in decode_benchmark(
            model, params, gen_steps=int(os.environ.get("DECODE_GEN_STEPS", "128")),
            batches=batches,
        ):
            print(
                f"DECODE_BATCH {row['batch']:4d}: {row['tok_per_s']:9.1f} tok/s "
                f"aggregate, {row['tok_per_s_per_stream']:7.1f} tok/s/stream, "
                f"{row['step_ms']:.2f} ms/step"
            )
