"""Analytic-vs-HLO cross-validation of the roofline FLOP model.

§Roofline derives FLOPs analytically because XLA counts scan bodies once
(DESIGN.md §6). This bench closes the loop: a single layer is lowered
standalone at the arch's FULL width with the attention chunk set to the
whole sequence (one chunk → the body IS the whole computation, so
``cost_analysis`` counts everything exactly once) and the HLO FLOPs are
compared against ``launch/analytic``'s per-layer formula. Agreement
within ~12 % (XLA counts some pointwise ops our napkin model rounds)
validates the §Roofline compute terms.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import repro.configs as C
from repro.launch import analytic
from repro.models import stack
from repro.models.rope import default_positions

CASES = [
    ("llama3.2-3b", 0),        # dense attention + SwiGLU
    ("deepseek-moe-16b", 2),   # attention + MoE (local dispatch path)
    ("recurrentgemma-9b", 0),  # RG-LRU + GeGLU
    ("xlstm-125m", 0),         # mLSTM block
]

B, S = 1, 512


def one_layer_flops(arch: str, layer_idx: int):
    cfg = dataclasses.replace(C.get(arch), chunk_len=S)
    kind = cfg.mixer_of(layer_idx)
    params = jax.eval_shape(
        lambda k: stack.init_layer(k, cfg, layer_idx, jnp.bfloat16),
        jax.random.PRNGKey(0),
    )
    params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), params
    )
    x = jnp.zeros((B, S, cfg.d_model), jnp.bfloat16)
    pos = default_positions(cfg, B, S)

    def f(p, x):
        y, aux, _ = stack.apply_layer(
            p, x, cfg, kind, cfg.uses_moe(layer_idx), pos, mode="forward"
        )
        return y

    compiled = jax.jit(f).lower(params, x).compile()
    hlo = float((compiled.cost_analysis() or {}).get("flops", 0.0))

    flags = analytic.ExecFlags(chunk_len=S)
    ana = analytic._mixer_flops(cfg, kind, B, S, S, flags, useful=False)
    if cfg.ffn_variant != "none" and kind not in ("mlstm", "slstm"):
        ana += (
            analytic._moe_flops(cfg, B, S, flags, useful=False)
            if cfg.uses_moe(layer_idx)
            else analytic._ffn_flops(cfg, B, S)
        )
    return hlo, ana, kind


def run():
    rows = []
    for arch, li in CASES:
        hlo, ana, kind = one_layer_flops(arch, li)
        ratio = ana / max(hlo, 1.0)
        rows.append((f"crossval/{arch}/{kind}_layer_flops_ratio", ratio,
                     "analytic/HLO ≈ 1"))
    return rows


if __name__ == "__main__":
    for name, v, ref in run():
        print(f"{name},{v:.4f},{ref}")
