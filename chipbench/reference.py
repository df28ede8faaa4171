"""The plain reference of the decoder LMs the benchmark trains.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
written from the layer equations and importing nothing of the program:

* embedding lookup; per layer ``x += attn(rms(x))``, ``x += ffn(rms(x))``;
  final rms norm; logits against the tied embedding; mean token
  cross-entropy plus ``aux_weight`` times the summed MoE balance loss;
* rms norm ``x / sqrt(mean(x^2) + eps) * (1 + w)`` (weights stored as an
  offset from one); rotary embedding on the two halves of each head;
  optional rms norm of q and k per head (qk-norm); causal softmax
  attention with ``kv_heads`` shared by ``heads / kv_heads`` query heads;
* SwiGLU feed-forward ``(silu(x Wg) * (x Wu)) Wd``;
* MoE (token choice): softmax router, top-k, gates renormalised over the
  k chosen, each expert takes at most ``C = int(cf * T * k / E)``
  assignments in token order and drops the rest; the balance loss is
  ``E * sum_e mean_t(p_te) * share_e`` over all T*k assignments.  Every
  expert is evaluated densely on every token and the dispatch weights
  pick the results: plain, not fast;
* AdamW with global-norm clipping, bias correction, decoupled weight
  decay, and linear warm-up into cosine decay, stepping ``count`` first.

Weights are drawn from the seed by the same key tree and scales the
program's initialiser uses (a fact of the configuration as run: random
weights from ``--seed``), so the reference needs nothing the program made.

``precision="fp8"`` is the control: every matmul input rounded to
float8 e4m3 with a per-tensor scale (amax / 448), accumulated in float32,
and every gradient flowing back into a matmul input rounded to e5m2 under
its own scale.
``fault="half_batch"`` leaves out half of the tokens and takes the mean
over the rest.  Both exist to be caught by the comparison.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: Query rows per attention block, and tokens per loss block: each block
#: is rematerialised, so the full-width reference fits one chip.
Q_BLOCK = 1024
LOSS_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class Arch:
    layers: int
    d: int
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    qk_norm: bool
    rope_theta: float
    eps: float
    d_ff: int
    experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    aux_weight: float = 0.01

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Arch":
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   vocab=c["vocab_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["head_dim"], qk_norm=c["qk_norm"],
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]),
                   d_ff=c["intermediate_size"],
                   experts=c.get("num_local_experts", 0),
                   top_k=c.get("num_experts_per_tok", 0),
                   capacity_factor=c.get("moe_capacity_factor", 1.25),
                   aux_weight=c.get("moe_aux_loss_weight", 0.01))


@dataclasses.dataclass(frozen=True)
class Adam:
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    clip_norm: float
    warmup_steps: int
    total_steps: int
    min_lr_ratio: float


# --------------------------------------------------------------- weights --

def _normal(key, shape, scale):
    return jax.random.normal(key, shape, F32) * scale


def _layer(a: Arch, key) -> Dict[str, Any]:
    k_attn, k_ffn = jax.random.split(key)
    ka = jax.random.split(k_attn, 4)
    attn = {
        "wq": _normal(ka[0], (a.d, a.heads, a.head_dim), 1 / math.sqrt(a.d)),
        "wk": _normal(ka[1], (a.d, a.kv_heads, a.head_dim),
                      1 / math.sqrt(a.d)),
        "wv": _normal(ka[2], (a.d, a.kv_heads, a.head_dim),
                      1 / math.sqrt(a.d)),
        "wo": _normal(ka[3], (a.heads, a.head_dim, a.d),
                      1 / math.sqrt(a.heads * a.head_dim)),
    }
    if a.qk_norm:
        attn["q_norm"] = jnp.zeros((a.head_dim,), F32)
        attn["k_norm"] = jnp.zeros((a.head_dim,), F32)
    out = {"ln1": jnp.zeros((a.d,), F32), "attn": attn,
           "ln2": jnp.zeros((a.d,), F32)}
    if a.experts:
        kf = jax.random.split(k_ffn, 5)
        E = a.experts
        out["moe"] = {
            "router": _normal(kf[0], (a.d, E), 0.02),
            "w_gate": _normal(kf[1], (E, a.d, a.d_ff), 1 / math.sqrt(E)),
            "w_up": _normal(kf[2], (E, a.d, a.d_ff), 1 / math.sqrt(E)),
            "w_down": _normal(kf[3], (E, a.d_ff, a.d), 1 / math.sqrt(a.d_ff)),
        }
    else:
        kf = jax.random.split(k_ffn, 3)
        out["mlp"] = {
            "w_gate": _normal(kf[0], (a.d, a.d_ff), 1 / math.sqrt(a.d)),
            "w_up": _normal(kf[1], (a.d, a.d_ff), 1 / math.sqrt(a.d)),
            "w_down": _normal(kf[2], (a.d_ff, a.d), 1 / math.sqrt(a.d_ff)),
        }
    return out


def init_params(a: Arch, seed: int) -> Dict[str, Any]:
    """float32 weights from ``seed``; layers stacked on a leading axis."""
    def make():
        ks = jax.random.split(jax.random.PRNGKey(seed), 8)
        layer_keys = jax.random.split(ks[2], a.layers)
        return {"embed": _normal(ks[0], (a.vocab, a.d), 0.02),
                "final_norm": jnp.zeros((a.d,), F32),
                "layers": jax.vmap(lambda k: _layer(a, k))(layer_keys)}
    return jax.jit(make)()


# --------------------------------------------------------------- forward --

def _round(x, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(F32) * s


@jax.custom_vjp
def _fp8(x):
    """Round to float8 e4m3 under a per-tensor scale (amax / 448), back
    to float32; the backward pass rounds the incoming gradient to e5m2
    under its own scale (amax / 57344), as fp8 training does."""
    return _round(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


class _Ops:
    def __init__(self, precision: str):
        if precision not in ("f32", "fp8"):
            raise ValueError(f"unknown reference precision {precision!r}")
        self.q = _fp8 if precision == "fp8" else (lambda x: x)

    def ein(self, eq, a, b):
        return jnp.einsum(eq, self.q(a), self.q(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=F32)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (B, S, H, D); positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(a: Arch, ops: _Ops, p, h):
    B, S, _ = h.shape
    q = ops.ein("bsd,dhk->bshk", h, p["wq"])
    k = ops.ein("bsd,dhk->bshk", h, p["wk"])
    v = ops.ein("bsd,dhk->bshk", h, p["wv"])
    if a.qk_norm:
        q = _rms(q, p["q_norm"], a.eps)
        k = _rms(k, p["k_norm"], a.eps)
    q, k = _rope(q, a.rope_theta), _rope(k, a.rope_theta)
    rep = a.heads // a.kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / math.sqrt(a.head_dim)

    @jax.checkpoint
    def block(qb, start):
        s = ops.ein("bqhd,bkhd->bhqk", qb, k) * scale
        qpos = start + jnp.arange(qb.shape[1])
        mask = jnp.arange(S)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return ops.ein("bhqk,bkhd->bqhd", w, v)

    qb = min(Q_BLOCK, S)
    outs = [block(q[:, i:i + qb], i) for i in range(0, S, qb)]
    out = jnp.concatenate(outs, axis=1)
    return ops.ein("bshk,hkd->bsd", out, p["wo"])


def _swiglu(ops: _Ops, p, h):
    g = ops.ein("...d,df->...f", h, p["w_gate"])
    u = ops.ein("...d,df->...f", h, p["w_up"])
    return ops.ein("...f,fd->...d", jax.nn.silu(g) * u, p["w_down"])


def _moe(a: Arch, ops: _Ops, p, h):
    B, S, D = h.shape
    T, E, K = B * S, a.experts, a.top_k
    x = h.reshape(T, D)
    probs = jax.nn.softmax(ops.ein("td,de->te", x, p["router"]), axis=-1)
    gates, ids = jax.lax.top_k(probs, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    cap = max(1, int(a.capacity_factor * T * K / E))
    onehot = jax.nn.one_hot(ids.reshape(-1), E, dtype=jnp.int32)  # (T*K, E)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    kept = (rank < cap).astype(F32).reshape(T, K)
    weight = jnp.einsum("tk,tke->te", gates * kept,
                        onehot.reshape(T, K, E).astype(F32))
    g = ops.ein("td,edf->tef", x, p["w_gate"])
    u = ops.ein("td,edf->tef", x, p["w_up"])
    y_e = ops.ein("tef,efd->ted", jax.nn.silu(g) * u, p["w_down"])
    y = jnp.einsum("te,ted->td", weight, y_e,
                   precision=jax.lax.Precision.HIGHEST)
    share = onehot.sum(axis=0).astype(F32) / (T * K)
    aux = E * jnp.sum(probs.mean(axis=0) * share)
    return y.reshape(B, S, D), aux


def loss_fn(a: Arch, params, tokens, labels, *, precision: str = "f32",
            fault: Optional[str] = None):
    """Mean next-token cross-entropy (+ MoE balance loss) of one batch."""
    ops = _Ops(precision)
    x = params["embed"][tokens]
    aux = jnp.zeros((), F32)
    for i in range(a.layers):
        lp = jax.tree_util.tree_map(lambda w, i=i: w[i], params["layers"])

        @jax.checkpoint
        def layer(x, lp):
            x = x + _attention(a, ops, lp["attn"], _rms(x, lp["ln1"], a.eps))
            h = _rms(x, lp["ln2"], a.eps)
            if a.experts:
                y, aux_l = _moe(a, ops, lp["moe"], h)
            else:
                y, aux_l = _swiglu(ops, lp["mlp"], h), jnp.zeros((), F32)
            return x + y, aux_l

        x, aux_l = layer(x, lp)
        aux = aux + aux_l
    x = _rms(x, params["final_norm"], a.eps)

    @jax.checkpoint
    def nll(xb, yb):
        logits = ops.ein("bsd,vd->bsv", xb, params["embed"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yb[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    B, S = labels.shape
    if fault == "half_batch":
        if B >= 2:
            x, labels = x[:B // 2], labels[:B // 2]
        else:
            x, labels = x[:, :S // 2], labels[:, :S // 2]
    elif fault is not None:
        raise ValueError(f"unknown reference fault {fault!r}")
    B, S = labels.shape
    n = min(LOSS_BLOCK, S)
    total = sum(nll(x[:, i:i + n], labels[:, i:i + n]) for i in range(0, S, n))
    return total / (B * S) + a.aux_weight * aux


# -------------------------------------------------------------- training --

def _schedule(o: Adam, count):
    c = count.astype(F32)
    warm = jnp.minimum(1.0, (c + 1) / max(1, o.warmup_steps))
    t = jnp.clip((c - o.warmup_steps) / max(1, o.total_steps - o.warmup_steps),
                 0.0, 1.0)
    cos = o.min_lr_ratio + (1 - o.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return o.lr * warm * cos


def _adamw(o: Adam, params, grads, mu, nu, count):
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, o.clip_norm / jnp.maximum(gnorm, 1e-9)) \
        if o.clip_norm > 0 else 1.0
    count = count + 1
    lr = _schedule(o, count)
    c1 = 1.0 - o.b1 ** count.astype(F32)
    c2 = 1.0 - o.b2 ** count.astype(F32)
    g = jax.tree_util.tree_map(lambda x: x * scale, grads)
    mu = jax.tree_util.tree_map(lambda m, x: o.b1 * m + (1 - o.b1) * x, mu, g)
    nu = jax.tree_util.tree_map(lambda n, x: o.b2 * n + (1 - o.b2) * x * x,
                                nu, g)
    params = jax.tree_util.tree_map(
        lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2) + o.eps)
                                  + o.weight_decay * p), params, mu, nu)
    return params, mu, nu, count, g


def leaf_norms(tree) -> Dict[str, float]:
    """Per-leaf float32 2-norms, keyed by the leaf's path."""
    norms = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))), t))(tree)
    return {jax.tree_util.keystr(k): float(v)
            for k, v in jax.tree_util.tree_leaves_with_path(norms)}


def _change_norms(params, p0) -> Dict[str, float]:
    """Per leaf, the float64 2-norm of ``params - p0`` (``p0`` on the
    host), one leaf and one block at a time."""
    out = {}
    for k, x in jax.tree_util.tree_leaves_with_path(params):
        a = np.asarray(jax.device_get(x)).reshape(-1)
        b = np.asarray(_at(p0, k)).reshape(-1)
        acc = 0.0
        for i in range(0, a.size, 1 << 24):
            d = a[i:i + (1 << 24)].astype(np.float64) - b[i:i + (1 << 24)]
            acc += float(np.dot(d, d))
        out[jax.tree_util.keystr(k)] = float(np.sqrt(acc))
    return out


def _at(tree, path):
    for k in path:
        tree = tree[k.key]
    return tree


def train(a: Arch, o: Adam, seed: int, batches: List[Dict[str, np.ndarray]],
          *, change_after: Optional[int] = None, precision: str = "f32",
          fault: Optional[str] = None, row_block: int = 1,
          device=None) -> Dict[str, Any]:
    """Run ``len(batches)`` steps from the seed's weights.  Returns each
    step's loss, the first step's clipped gradient norms per leaf, and the
    norm per leaf of the parameters' change over the first
    ``change_after`` steps (all of them by default).

    Rows are taken ``row_block`` at a time and their gradients averaged,
    which equals the batch's gradient only where rows do not interact:
    dense layers.  A MoE layer routes over all tokens of the batch, so a
    MoE batch is taken whole.
    """
    device = device or jax.devices()[0]
    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        params = init_params(a, seed)
        # The starting weights wait on the host: the device holds the
        # weights, the moments, a gradient and the step's temporaries.
        p0 = jax.device_get(params)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        count = jnp.zeros((), jnp.int32)
        grad = jax.jit(jax.value_and_grad(
            lambda p, t, y: loss_fn(a, p, t, y, precision=precision,
                                    fault=fault)))
        update = jax.jit(lambda p, g, m, n, c: _adamw(o, p, g, m, n, c),
                         donate_argnums=(0, 2, 3))
        losses, first_grad, change = [], None, None
        n_change = len(batches) if change_after is None else change_after
        for i, b in enumerate(batches):
            B = b["tokens"].shape[0]
            rb = B if a.experts or fault == "half_batch" else row_block
            if rb >= B:
                loss, g = grad(params, jnp.asarray(b["tokens"]),
                               jnp.asarray(b["labels"]))
            else:
                loss, g = 0.0, None
                for r in range(0, B, rb):
                    li, gi = grad(params, jnp.asarray(b["tokens"][r:r + rb]),
                                  jnp.asarray(b["labels"][r:r + rb]))
                    w = min(rb, B - r) / B
                    loss = loss + li * w
                    g = jax.tree_util.tree_map(lambda x: x * w, gi) \
                        if g is None else jax.tree_util.tree_map(
                            lambda x, y: x + y * w, g, gi)
                    del gi
            params, mu, nu, count, gc = update(params, g, mu, nu, count)
            losses.append(float(loss))
            if i == 0:
                first_grad = leaf_norms(gc)
            del g, gc
            if i == n_change - 1:
                change = _change_norms(params, p0)
                del p0
        del params, mu, nu
    return {"losses": losses, "grad_norms": first_grad,
            "update_norms": change}
