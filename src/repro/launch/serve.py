"""Serving launcher: batched prefill + decode with a KV cache.

``python -m repro.launch.serve --arch qwen2.5-3b --tokens 32 --batch 4``

Runs the smoke config on CPU (``--full`` for real hardware).  Exercises
the serve path the decode_* dry-run cells lower: prefill the prompt, then
step the sequence-shardable cache one token at a time.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.models import lm
from repro.runtime.fault_tolerance import elastic_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args()

    spec = registry.get(args.arch)
    cfg = spec.model if args.full else spec.smoke
    mesh = elastic_mesh(args.model_parallel)
    max_seq = args.prompt_len + args.tokens

    with jax.set_mesh(mesh):
        params, _ = lm.init(cfg, jax.random.PRNGKey(0))
        rng = jax.random.PRNGKey(1)
        prompt = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                    cfg.vocab)
        cache = lm.init_cache(cfg, args.batch, max_seq, dtype=jnp.float32)

        decode = jax.jit(lambda p, t, c: lm.decode(cfg, p, t, c))

        @jax.jit
        def prefill(p, prompt_toks, c):
            # The whole prompt in ONE dispatch: scan the single-token
            # decode over prompt positions inside a single jit, instead of
            # O(prompt_len) separate dispatches (each one a full host
            # round-trip).  The cache carry is scan-stable because its
            # fill level is a traced int32 scalar.
            def step(c, tok):
                logits, c = lm.decode(cfg, p, tok[:, None], c)
                return c, logits

            c, all_logits = jax.lax.scan(
                step, c, jnp.moveaxis(prompt_toks, 1, 0))
            return all_logits[-1], c

        t0 = time.time()
        logits, cache = prefill(params, prompt, cache)
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0

        # Timed loop is decode-only: one token per dispatch, by design.
        out = []
        t0 = time.time()
        for _ in range(args.tokens):
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            out.append(tok)
            logits, cache = decode(params, tok, cache)
        jax.block_until_ready(logits)
        dt = time.time() - t0
        n_prefill = args.batch * args.prompt_len
        n_decode = args.batch * args.tokens
        print(f"[serve] {cfg.name}: prefill {n_prefill} tokens in "
              f"{t_prefill:.2f}s (one dispatch), decode {n_decode} tokens "
              f"in {dt:.2f}s ({n_decode / dt:.1f} tok/s, "
              f"batch={args.batch})")
        print("[serve] sample continuation:",
              jnp.concatenate(out, 1)[0, :16].tolist())


if __name__ == "__main__":
    main()
