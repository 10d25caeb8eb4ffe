"""Plain float32 reference of the benchmarked training job.

Written from the configuration files under `configs/` (Hugging Face key names)
and the traffic mix's protocol parameters; imports nothing of the program
under test. It is the yardstick that decides `correct`: M regions, each
training its own replica with AdamW from weights made here from the seed, and
the CoCoDC protocol between them (paper Algorithm 1), at the steps the run's
host scheduler chose.

Two halves. The protocol (`adamw_step`, `fragments`, `CoCoDC`, `train`) is
generic over the parameter pytree and takes the model as a module. The
dense model below is the default, for a configuration without a
`"reference"` key: token embedding, `L` pre-norm blocks of RMSNorm, grouped
query attention with rotary positions (rotate-half form), RMSNorm and a
SwiGLU MLP, then a final RMSNorm and a tied or untied output head.

A configuration with `"reference": "<name>"` brings its own model as
`references/<name>.py` (`Registry.reference`), a module that imports
nothing of the program and exports, as this one does:

  * `dims(cfg)`: its sizes, with at least `L`, `H` (query heads) and `hd`
    (head size), which the causal attention count in `flops.py` and the
    roofline readers use;
  * `init_params(cfg, key)`: float32 weights from the seed's key, the
    program's own for the same key, in a pytree whose leaf paths are the
    program's; leaves stacked by layer sit under `['layers']`, which
    `fragments` splits by row;
  * `loss(cfg, params, tokens, labels, lowp)`: the mean next-token NLL of a
    (B, S) batch, every matmul through `_mm` so that `lowp="fp8"` is the
    control;
  * `matmul_params_per_token(cfg)`: the weights one token multiplies with
    (an MoE layer counts its router and `top_k` experts);
  * `program_fields(cfg)`: {attribute path of the program's `ModelConfig`:
    value}, every size the program has to agree on (`train_cell.
    model_config` refuses a path it cannot resolve and any disagreement).

Every matmul goes through `_mm`, which runs at `Precision.HIGHEST` (a float32
matmul on the TPU otherwise rounds its inputs to bfloat16). With
`lowp="fp8"` the inputs of every matmul are first rounded to float8 e4m3:
that is the control, one precision below the bfloat16 compute the
configurations state.
"""
from __future__ import annotations

import math
from types import ModuleType
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, lowp: Optional[str]):
    if lowp is None:
        return x.astype(jnp.float32)
    if lowp == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"unknown lowp {lowp!r}")


def _mm(spec: str, a, b, lowp: Optional[str]):
    return jnp.einsum(spec, _round(a, lowp), _round(b, lowp),
                      precision=HIGHEST, preferred_element_type=jnp.float32)


# ----------------------------------------------------------------- weights


def dims(cfg: Dict) -> Dict:
    """The sizes the reference needs, from a configuration file's keys."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {
        "L": int(cfg["num_hidden_layers"]), "D": d, "H": h,
        "KV": int(cfg["num_key_value_heads"]),
        "hd": int(cfg.get("head_dim") or d // h),
        "F": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "tied": bool(cfg["tie_word_embeddings"]),
    }


def program_fields(cfg: Dict) -> Dict:
    """The program's `ModelConfig` fields this model fixes; `moe` None, so
    a program with sparse experts is refused against a dense file."""
    m = dims(cfg)
    return {"n_layers": m["L"], "d_model": m["D"], "n_heads": m["H"],
            "n_kv_heads": m["KV"], "resolved_head_dim": m["hd"],
            "d_ff": m["F"], "vocab": m["V"], "rms_eps": m["eps"],
            "rope_theta": m["theta"], "tie_embeddings": m["tied"],
            "moe": None}


def matmul_params_per_token(cfg: Dict) -> int:
    """Weights each token multiplies with: attention projections, the
    SwiGLU MLP and the output head. The embedding lookup is a gather and
    counts nothing."""
    m = dims(cfg)
    D, F, H, KV, hd = m["D"], m["F"], m["H"], m["KV"], m["hd"]
    attn = D * H * hd * 2 + D * KV * hd * 2
    return m["L"] * (attn + 3 * D * F) + D * m["V"]


def _dense(key, shape, fan_in):
    return jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))


def init_params(cfg: Dict, key):
    """Seeded weights in float32: normal(0, 1/fan_in) matrices, normal(0,
    0.02) embedding, unit norms. The key tree (split 8 at the top; 6 for
    attention) is the one the configuration's weights are defined by, so the
    same seed gives the program's weights."""
    m = dims(cfg)
    L, D, F, V, H, KV, hd = (m[k] for k in ("L", "D", "F", "V", "H", "KV",
                                            "hd"))
    ks = jax.random.split(key, 8)
    ka = jax.random.split(ks[0], 6)
    layers = {
        "attn": {"wq": _dense(ka[0], (L, D, H * hd), D),
                 "wk": _dense(ka[1], (L, D, KV * hd), D),
                 "wv": _dense(ka[2], (L, D, KV * hd), D),
                 "wo": _dense(ka[3], (L, H * hd, D), H * hd)},
        "ln1": jnp.ones((L, D)), "ln2": jnp.ones((L, D)),
        "mlp": {"w_gate": _dense(ks[2], (L, D, F), D),
                "w_up": _dense(ks[3], (L, D, F), D),
                "w_down": _dense(ks[4], (L, F, D), F)},
    }
    params = {"embed": jax.random.normal(ks[5], (V, D)) * 0.02,
              "layers": layers, "final_norm": jnp.ones((D,))}
    if not m["tied"]:
        params["lm_head"] = _dense(ks[6], (D, V), D)
    return params


# ----------------------------------------------------------------- forward


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, heads, hd) at positions 0..S-1; rotate-half form."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(x, a, m, lowp):
    """Causal GQA over one sequence. x: (S, D)."""
    S = x.shape[0]
    H, KV, hd = m["H"], m["KV"], m["hd"]
    q = _rope(_mm("sd,de->se", x, a["wq"], lowp).reshape(S, H, hd), m["theta"])
    k = _rope(_mm("sd,de->se", x, a["wk"], lowp).reshape(S, KV, hd),
              m["theta"])
    v = _mm("sd,de->se", x, a["wv"], lowp).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)           # q head h reads kv h // G
    v = jnp.repeat(v, H // KV, axis=1)
    s = _mm("qhd,khd->hqk", q, k, lowp) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = _mm("hqk,khd->qhd", p, v, lowp).reshape(S, H * hd)
    return _mm("se,ed->sd", o, a["wo"], lowp)


def _swiglu(x, wg, wu, wd, lowp):
    h = jax.nn.silu(_mm("...d,df->...f", x, wg, lowp)) * _mm(
        "...d,df->...f", x, wu, lowp)
    return _mm("...f,fd->...d", h, wd, lowp)


def hidden(cfg: Dict, params, tokens, lowp: Optional[str] = None):
    """Final hidden states of a (B, S) batch. The blocks run in a scan over
    the layer axis, each recomputed in the backward pass, so a step holds
    one block's activations at a time."""
    m = dims(cfg)

    @jax.checkpoint
    def block(x, lp):
        x = x + jax.vmap(lambda r: _attention(
            _rms(r, lp["ln1"], m["eps"]), lp["attn"], m, lowp))(x)
        h = _rms(x, lp["ln2"], m["eps"])
        return x + _swiglu(h, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                           lp["mlp"]["w_down"], lowp), None

    x, _ = jax.lax.scan(block, params["embed"][tokens], params["layers"])
    return _rms(x, params["final_norm"], m["eps"])


def head(cfg: Dict, params):
    return params["embed"].T if dims(cfg)["tied"] else params["lm_head"]


def loss(cfg: Dict, params, tokens, labels, lowp: Optional[str] = None):
    """Mean next-token NLL over a (B, S) batch."""
    hs = hidden(cfg, params, tokens, lowp)
    lg = _mm("bsd,dv->bsv", hs, head(cfg, params), lowp)
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, labels[..., None], -1)[..., 0]
    return jnp.mean(nll)


# ------------------------------------------------------------------- AdamW


def lr_at(step, run: Dict):
    """Linear warm-up from 0, then cosine to a tenth of the peak."""
    base, warm, total = (float(run["inner_lr"]), int(run["warmup_steps"]),
                         int(run["total_steps"]))
    step = float(step)
    if step < warm:
        return base * step / max(1, warm)
    prog = min(max((step - warm) / max(1, total - warm), 0.0), 1.0)
    return 0.1 * base + 0.9 * base * 0.5 * (1 + math.cos(math.pi * prog))


def norms(tree) -> Dict[str, float]:
    """{leaf path: float32 norm} of a pytree."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(a)))
            for p, a in flat}


def adamw_step(model: ModuleType, cfg: Dict, run: Dict,
               lowp: Optional[str] = None, batch_frac: float = 1.0):
    """One jitted AdamW step of `model`'s loss (b1 0.9, b2 0.95, eps 1e-8,
    decoupled decay, clip by global norm 1): `(p, mu, nu, tokens, labels,
    count, lr) -> (p, mu, nu, loss, norms of the clipped gradient)`.
    `batch_frac` < 1 keeps only that leading share of the batch's rows (a
    planted fault, never used for the reference itself)."""
    b1, b2, eps = 0.9, 0.95, 1e-8
    wd, clip = float(run["weight_decay"]), 1.0

    def step(p, mu, nu, tok, lab, count, lr):
        if batch_frac < 1.0:
            keep = max(1, int(tok.shape[0] * batch_frac))
            tok, lab = tok[:keep], lab[:keep]
        val, g = jax.value_and_grad(
            lambda q: model.loss(cfg, q, tok, lab, lowp))(p)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / (gn + 1e-9)), g)
        mu = jax.tree.map(lambda m_, x: b1 * m_ + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        p = jax.tree.map(
            lambda p_, m_, v: p_ - lr * ((m_ / c1) / (jnp.sqrt(v / c2) + eps)
                                         + wd * p_), p, mu, nu)
        return p, mu, nu, val, norms(g)

    return jax.jit(step, donate_argnums=(0, 1, 2))


# --------------------------------------------------------- CoCoDC protocol


def fragments(params, K: int) -> Dict[str, List]:
    """Streaming DiLoCo's strided fragments: row l of every leaf stacked by
    layer goes to fragment l % K; every other leaf goes whole, biggest first
    (ties in key order), to the fragment with the fewest bytes so far (ties
    to the lowest index). Returns {leaf path: [index of fragment p]}, where
    an index is the layer rows, `Ellipsis` for the whole leaf, or None."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    plan, size = {}, np.zeros(K)
    whole = []
    for path, a in flat:
        key = jax.tree_util.keystr(path)
        if key.startswith("['layers']"):
            rows = [np.arange(p, a.shape[0], K) for p in range(K)]
            plan[key] = [r if len(r) else None for r in rows]
            size += [len(r) * a.size // a.shape[0] for r in rows]
        else:
            whole.append((key, a.size))
    for key, n in sorted(whole, key=lambda kv: -kv[1]):
        owner = int(np.argmin(size))
        size[owner] += n
        plan[key] = [Ellipsis if p == owner else None for p in range(K)]
    return plan


@jax.jit
def _pseudograd(snaps, theta):
    return jnp.mean(snaps - theta[None], 0)


@jax.jit
def _outer(theta, mom, delta, lr, mu):
    """Outer Nesterov step on one leaf's fragment rows:
    m <- mu m + delta, theta <- theta + lr (delta + mu m)."""
    mom = mu * mom + delta
    return theta + lr * (delta + mu * mom), mom


@jax.jit
def _compensate(now, snap, g_new, tau, lam, H):
    """Algorithm 1 (Eqs. 4, 7, 8): the local progress since the snapshot,
    g = (now - snap) / tau, corrected by lam g*g*(g_new - snap) / H, is
    re-applied for tau steps on top of the new global fragment."""
    g = (now - snap) / tau
    return g_new + tau * (g + lam * g * g * (g_new - snap) / H)


class CoCoDC:
    """The protocol's state between M regions: the global model, its outer
    momentum, and each in-flight fragment's per-region snapshots."""

    def __init__(self, params0, mix: Dict, exchange: bool = True):
        self.K = int(mix["num_fragments"])
        self.plan = fragments(params0, self.K)
        self.theta = _flat(params0)
        self.mom = {k: jnp.zeros_like(v) for k, v in self.theta.items()}
        self.lr, self.mu = float(mix["outer_lr"]), float(mix["outer_momentum"])
        self.lam, self.H = float(mix["comp_lambda"]), float(mix["local_steps"])
        self.exchange = exchange
        self.inflight: Dict[int, Tuple[int, Dict, Dict]] = {}

    def initiate(self, frag: int, t: int, regions: Sequence[Dict]):
        """Snapshot each region's fragment and take the pseudo-gradient, the
        mean over regions of (snapshot - global)."""
        snaps = {k: jnp.stack([r[k][idx[frag]] for r in regions])
                 for k, idx in self.plan.items() if idx[frag] is not None}
        delta = {k: _pseudograd(s, self.theta[k][self.plan[k][frag]])
                 for k, s in snaps.items()}
        self.inflight[frag] = (t, snaps, delta)

    def deliver(self, frag: int, t: int, regions: List[Dict]):
        """The outer step on the fragment, then Algorithm 1 in each region
        with the actual overlap depth tau = t - t_init."""
        t_init, snaps, delta = self.inflight.pop(frag)
        tau = float(max(1, t - t_init))
        for k, s in snaps.items():
            idx = self.plan[k][frag]
            g_new, mom = _outer(self.theta[k][idx], self.mom[k][idx],
                                delta[k], self.lr, self.mu)
            self.theta[k] = _set(self.theta[k], idx, g_new)
            self.mom[k] = _set(self.mom[k], idx, mom)
            if not self.exchange:
                continue
            for m, r in enumerate(regions):
                r[k] = _set(r[k], idx, _compensate(
                    r[k][idx], s[m], g_new, tau, self.lam, self.H))


def _flat(tree) -> Dict:
    return {jax.tree_util.keystr(p): a
            for p, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _set(a, idx, v):
    return v if idx is Ellipsis else a.at[idx].set(v)


def train(model: ModuleType, cfg: Dict, params0, batches, mix: Dict, syncs,
          marks: Sequence[int], lowp: Optional[str] = None,
          batch_frac: float = 1.0, exchange: bool = True) -> Dict:
    """M regions of `model` (a model module; this one for the dense model)
    trained together for T steps from `params0`.

    batches[m][t] = (tokens, labels) of region m at step t. `syncs` lists
    (fragment, t_init, t_deliver): at the end of step t every delivery due
    is applied first, then every initiation. Returns the losses (M, T), the
    first clipped gradient's leaf norms per region, and the leaf norms of
    each region's change from `params0` after each step count in `marks`.
    `exchange=False` is a planted fault: the outer step runs but no region
    receives its result."""
    M, T = len(batches), len(batches[0])
    step = adamw_step(model, cfg, mix, lowp, batch_frac)
    treedef = jax.tree_util.tree_structure(params0)
    regions = []
    for _ in range(M):
        p = jax.tree.map(jnp.copy, params0)
        regions.append([p, jax.tree.map(jnp.zeros_like, p),
                        jax.tree.map(jnp.zeros_like, p)])
    proto = CoCoDC(params0, mix, exchange)
    losses = [[None] * T for _ in range(M)]
    grad, change = [None] * M, {n: [None] * M for n in marks}
    base = _flat(params0)
    for t in range(T):
        for m in range(M):
            tok, lab = (jnp.asarray(x) for x in batches[m][t])
            p, mu, nu, val, gn = step(*regions[m], tok, lab,
                                      float(t + 1), lr_at(t, mix))
            regions[m] = [p, mu, nu]
            losses[m][t] = val
            if t == 0:
                grad[m] = gn
        due = [(f, ti) for f, ti, td in syncs if td == t]
        starts = [f for f, ti, td in syncs if ti == t]
        if due or starts:
            flat = [_flat(r[0]) for r in regions]
            for frag, _ in sorted(due, key=lambda d: d[1]):
                proto.deliver(frag, t, flat)
            for frag in starts:
                proto.initiate(frag, t, flat)
            for m in range(M):
                regions[m][0] = jax.tree_util.tree_unflatten(
                    treedef, list(flat[m].values()))
        if t + 1 in change:
            change[t + 1] = [
                {k: jnp.sqrt(jnp.sum(jnp.square(v - base[k])))
                 for k, v in _flat(r[0]).items()} for r in regions]
    stack = lambda rows: {k: np.array([float(r[k]) for r in rows])
                          for k in rows[0]}
    return {"losses": np.array([[float(v) for v in r] for r in losses]),
            "grad": stack(grad),
            "change": {n: stack(rows) for n, rows in change.items()}}
