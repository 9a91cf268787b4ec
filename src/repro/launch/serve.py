"""Serving launcher: batched prefill + autoregressive decode.

Runs a real (smoke-scale by default) model on the host mesh: prefills a
batch of prompts, then decodes greedily token-by-token against the KV /
SSM caches, reporting per-phase throughput. The same decode_step the
dry-run lowers for the production mesh is what runs here.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2_2b \
      --batch 4 --prompt-len 32 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs, smoke_variant
from repro.launch.compile_cache import enable_compile_cache
from repro.models import encdec as ed
from repro.models import frontends as fe
from repro.models import transformer as tf


_JITTED_STEPS: dict = {}


def _jitted_step(step):
    """jit each decode step ONCE per process, at stable function identity
    (step fns are module-level, cfg is a frozen hashable config), so the
    compile cache is shared across generate() calls instead of retracing
    through a fresh per-call lambda."""
    if step not in _JITTED_STEPS:
        _JITTED_STEPS[step] = jax.jit(step, static_argnums=0)
    return _JITTED_STEPS[step]


def generate(cfg, params, prompt: jax.Array, gen_len: int,
             frames=None) -> tuple[jax.Array, dict]:
    """Greedy decode. prompt [B, S0] -> tokens [B, S0+gen_len]."""
    b, s0 = prompt.shape
    max_len = s0 + gen_len

    if cfg.family == "encdec":
        caches = ed.init_encdec_caches(cfg, params, frames, b, max_len)
        step = ed.decode_step_encdec
    else:
        caches = tf.init_caches(cfg, b, max_len)
        step = tf.decode_step

    jitted = _jitted_step(step)

    # prefill via the decode path one token at a time would be wasteful on
    # real hardware; here prefill = teacher-forcing the prompt through the
    # cached step (exercises exactly the serving cache path).
    jax.block_until_ready((params, prompt))
    t0 = time.time()
    tokens = prompt
    out = None
    for i in range(s0):
        out = jitted(cfg, params, tokens[:, i:i + 1], caches,
                     jnp.asarray(i, jnp.int32))
        caches = out.caches
    # async dispatch: without this barrier the timer reads queueing time,
    # not prefill time
    jax.block_until_ready(out.logits)
    prefill_sec = time.time() - t0

    t0 = time.time()
    cur = jnp.argmax(out.logits[:, -1], -1)[:, None].astype(jnp.int32)
    generated = [cur]
    for i in range(s0, max_len - 1):
        out = jitted(cfg, params, cur, caches, jnp.asarray(i, jnp.int32))
        caches = out.caches
        cur = jnp.argmax(out.logits[:, -1], -1)[:, None].astype(jnp.int32)
        generated.append(cur)
    jax.block_until_ready(cur)
    decode_sec = time.time() - t0

    tokens = jnp.concatenate([prompt] + generated, axis=1)
    stats = {
        "prefill_sec": prefill_sec,
        "decode_sec": decode_sec,
        "decode_tok_per_sec": b * (len(generated)) / max(decode_sec, 1e-9),
    }
    return tokens, stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2_2b", choices=list_archs())
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = smoke_variant(cfg)
    key = jax.random.key(args.seed)
    print(f"arch={cfg.name} family={cfg.family} params~{cfg.n_params():,}")

    frames = None
    if cfg.family == "encdec":
        params = ed.init_encdec(cfg, key)
        frames = fe.audio_frames_stub(cfg, key, args.batch, 64)
    else:
        params = tf.init_decoder_lm(cfg, key)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size, jnp.int32)
    tokens, stats = generate(cfg, params, prompt, args.gen, frames=frames)
    print(f"generated {tokens.shape} | prefill {stats['prefill_sec']:.2f}s "
          f"| decode {stats['decode_sec']:.2f}s "
          f"({stats['decode_tok_per_sec']:.1f} tok/s)")
    print("sample:", tokens[0, args.prompt_len:args.prompt_len + 12])


if __name__ == "__main__":
    main()
