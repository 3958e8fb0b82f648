"""Serving example: batched prefill + greedy decode with a KV cache
(optionally int8-quantized).

    PYTHONPATH=src python examples/serve_lm.py [--quant-kv]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduce_config
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--quant-kv", action="store_true")
ap.add_argument("--batch", type=int, default=4)
ap.add_argument("--prompt-len", type=int, default=64)
ap.add_argument("--gen", type=int, default=32)
args = ap.parse_args()

cfg = reduce_config(get_config("qwen1.5-4b"),
                    num_layers=4, d_model=128, num_heads=8, num_kv_heads=4,
                    head_dim=16, d_ff=512, vocab_size=4096)
model = get_model(cfg)
params = model.init(jax.random.PRNGKey(0))

B, P, G = args.batch, args.prompt_len, args.gen
prompts = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, cfg.vocab_size)
cache = model.init_cache(B, P + G, dtype=jnp.float32, quant_kv=args.quant_kv)

decode = jax.jit(steps_mod.make_decode_step(model), donate_argnums=(1,))

t0 = time.perf_counter()
logits, cache = model.prefill(params, prompts, cache)
tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
prefill_s = time.perf_counter() - t0

out = [tok]
t0 = time.perf_counter()
for _ in range(G - 1):
    tok, cache = decode(params, cache, {"tokens": tok})
    tok = tok[:, None]
    out.append(tok)
jax.block_until_ready(tok)
decode_s = time.perf_counter() - t0

gen = jnp.concatenate(out, axis=1)
kv = "int8" if args.quant_kv else "bf16/f32"
print(f"served batch={B} prompt={P} gen={G} (kv cache: {kv})")
print(f"prefill {prefill_s*1e3:.1f} ms; decode {decode_s/max(G-1,1)*1e3:.2f} "
      f"ms/token; sample tokens: {gen[0, :10].tolist()}")
