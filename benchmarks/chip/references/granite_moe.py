"""Plain float32 reference of Granite 3.0's MoE decoder
(`GraniteMoeForCausalLM`), at one chip's share of its experts.

Written from the published equations; imports nothing of the program. The
contract is `reference.py`'s docstring. Per layer, with the file's four
muP scalars:

    x0 = embedding_multiplier * embed(tokens)
    x += residual_multiplier * Attn(RMSNorm(x)),  scores q.k * attention_multiplier
    x += residual_multiplier * MoE(RMSNorm(x))
    logits = (RMSNorm(x) @ embed.T) / logits_scaling

MoE (HF `GraniteMoeTopKGating`, `GraniteMoeParallelExperts`): a router of
`num_local_experts * expert_shards` outputs with no bias; the top
`num_experts_per_tok` of its logits, gates the softmax over those; the
output the gated sum of SwiGLU experts, W_down(silu(W_gate x) * W_up x).
Dropless: every routed pair counts. This chip holds experts
[expert_shard * held, (expert_shard + 1) * held), where `held` is the
file's `num_local_experts`; pairs routed to experts held elsewhere add
nothing, here as in the program. Each held expert is computed on every
token and weighted by its gate, zero where the token did not choose it.

The loss is the mean next-token NLL plus the load-balancing term of HF's
`load_balancing_loss_func`: `router_aux_loss_coef` times E times the dot of
each expert's share of the top-k choices and its mean router probability,
both over every layer's tokens taken as one batch.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.chip import reference
from benchmarks.chip.reference import _mm, _rms, _rope


def dims(cfg: Dict) -> Dict:
    """The sizes and scalars the model needs, from the file's keys."""
    m = reference.dims(cfg)
    held, shards = int(cfg["num_local_experts"]), int(cfg["expert_shards"])
    m.update(E=held * shards, E_held=held,
             first=int(cfg["expert_shard"]) * held, shards=shards,
             shard=int(cfg["expert_shard"]),
             top_k=int(cfg["num_experts_per_tok"]),
             aux_coef=float(cfg["router_aux_loss_coef"]),
             emb_mult=float(cfg["embedding_multiplier"]),
             attn_mult=float(cfg["attention_multiplier"]),
             resid_mult=float(cfg["residual_multiplier"]),
             logits_scaling=float(cfg["logits_scaling"]))
    return m


def program_fields(cfg: Dict) -> Dict:
    m = dims(cfg)
    return {"n_layers": m["L"], "d_model": m["D"], "n_heads": m["H"],
            "n_kv_heads": m["KV"], "head_dim": m["hd"], "d_ff": m["F"],
            "vocab": m["V"], "tie_embeddings": m["tied"],
            "rms_eps": m["eps"], "rope_theta": m["theta"],
            "attn_bias": False, "qk_norm": False,
            "moe.num_experts": m["E"], "moe.top_k": m["top_k"],
            "moe.expert_shards": m["shards"],
            "moe.expert_shard": m["shard"],
            "moe.load_balance_coef": m["aux_coef"],
            "embedding_multiplier": m["emb_mult"],
            "attention_multiplier": m["attn_mult"],
            "residual_multiplier": m["resid_mult"],
            "logits_scaling": m["logits_scaling"]}


def matmul_params_per_token(cfg: Dict) -> float:
    """Weights each token multiplies with here: attention projections, the
    whole router, top_k * held / E experts (the pairs this chip's experts
    take on average), and the tied head once."""
    m = dims(cfg)
    D, F, H, KV, hd = m["D"], m["F"], m["H"], m["KV"], m["hd"]
    attn = D * H * hd * 2 + D * KV * hd * 2
    experts = m["top_k"] * m["E_held"] / m["E"] * 3 * D * F
    return m["L"] * (attn + D * m["E"] + experts) + D * m["V"]


def _dense(key, shape, fan_in):
    return jax.random.normal(key, shape) * (1.0 / fan_in ** 0.5)


def init_params(cfg: Dict, key):
    """Seeded float32 weights: normal(0, 1/fan_in) matrices, normal(0,
    0.02) embedding, unit norms. The key tree: split 8 at the top, 6 for
    attention, 4 for the MoE layer (router, gate, up, down); expert e's
    matrices are drawn from its key folded with e, its global index."""
    m = dims(cfg)
    L, D, F, V, H, KV, hd, E = (m[k] for k in ("L", "D", "F", "V", "H", "KV",
                                               "hd", "E"))
    ks = jax.random.split(key, 8)
    ka = jax.random.split(ks[0], 6)
    km = jax.random.split(ks[1], 4)
    held = jnp.arange(m["first"], m["first"] + m["E_held"])

    def experts(k, shape, fan_in):
        w = jax.vmap(lambda e: _dense(jax.random.fold_in(k, e), shape,
                                      fan_in))(held)
        return jnp.moveaxis(w, 0, 1)                     # (L, held, ...)

    layers = {
        "attn": {"wq": _dense(ka[0], (L, D, H * hd), D),
                 "wk": _dense(ka[1], (L, D, KV * hd), D),
                 "wv": _dense(ka[2], (L, D, KV * hd), D),
                 "wo": _dense(ka[3], (L, H * hd, D), H * hd)},
        "ln1": jnp.ones((L, D)), "ln2": jnp.ones((L, D)),
        "moe": {"router": _dense(km[0], (L, D, E), D),
                "w_gate": experts(km[1], (L, D, F), D),
                "w_up": experts(km[2], (L, D, F), D),
                "w_down": experts(km[3], (L, F, D), F)},
    }
    return {"embed": jax.random.normal(ks[5], (V, D)) * 0.02,
            "layers": layers, "final_norm": jnp.ones((D,))}


def _attention(x, a, m, lowp):
    """Causal GQA over one sequence, scores scaled by attention_multiplier.
    x: (S, D)."""
    S = x.shape[0]
    H, KV, hd = m["H"], m["KV"], m["hd"]
    q = _rope(_mm("sd,de->se", x, a["wq"], lowp).reshape(S, H, hd), m["theta"])
    k = _rope(_mm("sd,de->se", x, a["wk"], lowp).reshape(S, KV, hd),
              m["theta"])
    v = _mm("sd,de->se", x, a["wv"], lowp).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)           # q head h reads kv h // G
    v = jnp.repeat(v, H // KV, axis=1)
    s = _mm("qhd,khd->hqk", q, k, lowp) * m["attn_mult"]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = _mm("hqk,khd->qhd", p, v, lowp).reshape(S, H * hd)
    return _mm("se,ed->sd", o, a["wo"], lowp)


def moe(h, p, m, lowp: Optional[str] = None):
    """One MoE layer over tokens h (T, D) at this chip's share: (the held
    experts' part of the output (T, D), the routing load (2, E): each
    expert's share of the top-k choices and its mean router
    probability)."""
    T = h.shape[0]
    logits = _mm("td,de->te", h, p["router"], lowp)
    top, chosen = jax.lax.top_k(logits, m["top_k"])
    gates = jax.nn.softmax(top, -1)                              # (T, K)
    held = m["first"] + jnp.arange(m["E_held"])
    weight = jnp.sum(gates[:, :, None] * (chosen[:, :, None] == held),
                     axis=1)                                     # (T, held)

    def expert(wg, wu, wd):
        a = jax.nn.silu(_mm("td,df->tf", h, wg, lowp)) * _mm(
            "td,df->tf", h, wu, lowp)
        return _mm("tf,fd->td", a, wd, lowp)

    ys = jax.vmap(expert)(p["w_gate"], p["w_up"], p["w_down"])  # (held, T, D)
    y = jnp.sum(weight.T[:, :, None] * ys, axis=0)
    share = jnp.zeros((m["E"],)).at[chosen.reshape(-1)].add(1.0) / T
    load = jnp.stack([share, jnp.mean(jax.nn.softmax(logits, -1), 0)])
    return y, load


def loss(cfg: Dict, params, tokens, labels, lowp: Optional[str] = None):
    """Mean next-token NLL over a (B, S) batch plus the balance term. The
    blocks run in a scan over the layer axis, each recomputed in the
    backward pass."""
    m = dims(cfg)
    r = m["resid_mult"]

    @jax.checkpoint
    def block(x, lp):
        B, S, D = x.shape
        x = x + r * jax.vmap(lambda row: _attention(
            _rms(row, lp["ln1"], m["eps"]), lp["attn"], m, lowp))(x)
        y, load = moe(_rms(x, lp["ln2"], m["eps"]).reshape(B * S, D),
                      lp["moe"], m, lowp)
        return x + r * y.reshape(B, S, D), load

    x, loads = jax.lax.scan(block, m["emb_mult"] * params["embed"][tokens],
                            params["layers"])
    hs = _rms(x, params["final_norm"], m["eps"])
    lg = _mm("bsd,dv->bsv", hs, params["embed"].T, lowp) / m["logits_scaling"]
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, labels[..., None], -1)[..., 0]
    share, prob = jnp.mean(loads, axis=0)
    return jnp.mean(nll) + m["aux_coef"] * m["E"] * jnp.sum(share * prob)
