"""The paper's technique inside the LM: CPD-factorized embedding tables.

Trains two small LMs — dense embedding vs rank-R CPD-factorized embedding
(cfg.cpd_embed_rank) — and shows the parameter savings with comparable
loss.  The factor gradients ARE spMTTKRPs of the token batch (see
repro/models/factorized_embed.py and its tests).

    PYTHONPATH=src python examples/factorized_embedding.py
"""
import dataclasses

import jax

from repro import optim
from repro.configs import get_config, reduce_config
from repro.data import TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import get_model
from repro.models import factorized_embed as fe
from repro.runtime import Trainer

enable_compile_cache()

base = dataclasses.replace(
    reduce_config(get_config("qwen1.5-4b")),
    vocab_size=8192, d_model=96, num_heads=6, num_kv_heads=2, head_dim=16,
    num_layers=2, d_ff=256,
)

for label, cfg in [
    ("dense-embed", base),
    ("cpd-embed-r32", dataclasses.replace(base, cpd_embed_rank=32)),
]:
    model = get_model(cfg)
    n = sum(int(x.size) for x in jax.tree.leaves(model.abstract_params()))
    pipe = TokenPipeline(cfg.vocab_size, batch=8, seq_len=64, seed=1)
    tr = Trainer(model, mesh=make_host_mesh(), pipeline=pipe,
                 opt_cfg=optim.AdamWConfig(lr=2e-3, warmup_steps=5,
                                           total_steps=60))
    h = tr.run(60, log_every=1000)
    extra = ""
    if cfg.cpd_embed_rank:
        extra = (f" (table compression "
                 f"{fe.compression_ratio(cfg.padded_vocab, cfg.d_model, cfg.cpd_embed_rank):.0f}x)")
    print(f"{label:14s}: params={n:>9,d} loss {h[0]['loss']:.3f} -> "
          f"{h[-1]['loss']:.3f}{extra}")
