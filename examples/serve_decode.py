"""Serving example: batched incremental decoding with a KV/SSM cache.

Loads (or initializes) a reduced gemma3-family model, prefills a prompt
batch via the decode path, then greedily generates tokens — demonstrating
the same serve_step the decode_32k / long_500k dry-runs lower, including
the local/global window pattern.

Run:  PYTHONPATH=src python examples/serve_decode.py
"""
import time

import jax
import jax.numpy as jnp

from repro.common.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models.model import Model


def main():
    enable_compile_cache()
    cfg = get_config("gemma3-1b").reduced()
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    B, prompt_len, gen_len = 4, 16, 24
    max_len = prompt_len + gen_len
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, prompt_len),
                                0, cfg.vocab_size)

    cache = model.init_cache(B, max_len)
    step = jax.jit(model.decode_step)

    # prefill by stepping the prompt through the cache
    t0 = time.time()
    logits = None
    for t in range(prompt_len):
        logits, cache = step(params, cache, prompt[:, t:t + 1], jnp.int32(t))
    print(f"prefill {prompt_len} tokens x {B} seqs: {time.time()-t0:.2f}s")

    # greedy decode
    t0 = time.time()
    out = []
    tok = jnp.argmax(logits, -1)[:, None]
    for t in range(prompt_len, max_len):
        out.append(tok)
        logits, cache = step(params, cache, tok, jnp.int32(t))
        tok = jnp.argmax(logits, -1)[:, None]
    gen = jnp.concatenate(out, axis=1)
    dt = time.time() - t0
    print(f"generated {gen_len} tokens x {B} seqs: {dt:.2f}s "
          f"({B * gen_len / dt:.1f} tok/s on CPU)")
    print("sample token ids:", gen[0, :12].tolist())


if __name__ == "__main__":
    main()
