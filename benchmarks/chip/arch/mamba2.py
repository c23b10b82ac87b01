"""Plain float32 reference of a Mamba-2 language model's training step, and
the seeded weights both the program and this reference start from.

Mamba-2 (arXiv:2405.21060), per layer, pre-norm residual:

    h        = rmsnorm(x) * norm1
    z|xBC|dt = h @ in_proj
    xBC      = silu(causal depthwise conv(xBC) + conv_b)       (width d_conv)
    x|B|C    = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    state_t  = exp(dt_t A) state_{t-1} + dt_t x_t B_t^T        (per head)
    y_t      = C_t state_t + D x_t
    out      = (rmsnorm(y * silu(z)) * norm) @ out_proj;   x = x + out

then ``rmsnorm(x) * final_norm`` and the tied unembedding ``x @ embed^T``.
The loss is the token mean of cross-entropy plus ``z_loss * lse^2``; the
optimizer is AdamW with global-norm clipping and bias correction.

The scan is computed in the state-space-dual chunked form (exact: an
intra-chunk quadratic term plus a state carried between chunks), in
float32 at ``Precision.HIGHEST``.  The gradient of a batch is the mean of
per-row gradients, taken a block of rows at a time so that the reference
fits one chip after the program's state is freed.

The sizes are the release's (vocabulary 50277 padded to 50288, chunk
256, RMSNorm epsilon 1e-5).  One departure from the published recipe,
shared with the program under test and stated in the configuration file:
weight decay is 0.

Nothing here imports the program.  The parameter tree has the program's
layout (layers stacked on a leading axis under ``blocks[0]``) so that the
same weights can be handed to it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


# ---------------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------------

def dims(c: dict) -> dict:
    d_inner = c["expand"] * c["d_model"]
    heads = d_inner // c["headdim"]
    gn = c["ngroups"] * c["d_state"]
    return {"D": c["d_model"], "L": c["n_layer"], "V": c["vocab_size"],
            "di": d_inner, "H": heads, "P": c["headdim"], "G": c["ngroups"],
            "N": c["d_state"], "K": c["d_conv"], "conv_ch": d_inner + 2 * gn,
            "proj": 2 * d_inner + 2 * gn + heads, "Q": c["chunk_size"]}


def layout(c: dict) -> dict:
    """Shapes of the parameter tree, in the program's layout."""
    d = dims(c)
    L = d["L"]
    mixer = {"in_proj": (L, d["D"], d["proj"]),
             "conv_w": (L, d["conv_ch"], d["K"]),
             "conv_b": (L, d["conv_ch"]),
             "A_log": (L, d["H"]), "D": (L, d["H"]), "dt_bias": (L, d["H"]),
             "norm": (L, d["di"]), "out_proj": (L, d["di"], d["D"])}
    return {"embed": (d["V"], d["D"]),
            "blocks": [{"norm1": (L, d["D"]), "mixer": mixer}],
            "rem": [], "final_norm": (d["D"],)}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _normal(key, shape, fan_in):
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)
            * fan_in ** -0.5)


@functools.partial(jax.jit, static_argnums=1)
def _make_params(key, frozen_c):
    c = dict(frozen_c)
    d = dims(c)
    L, H = d["L"], d["H"]
    ks = jax.random.split(key, 6)
    # A in [1, 16] and dt in [1e-3, 1e-1] log-uniform, as in the Mamba-2
    # release; dt_bias is the inverse softplus of dt
    a = jax.random.uniform(ks[3], (L, H), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(ks[4], (L, H), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    mixer = {
        "in_proj": _normal(ks[0], (L, d["D"], d["proj"]), d["D"]),
        "conv_w": _normal(ks[1], (L, d["conv_ch"], d["K"]), d["K"]),
        "conv_b": jnp.zeros((L, d["conv_ch"]), jnp.float32),
        "A_log": jnp.log(a),
        "D": jnp.ones((L, H), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "norm": jnp.ones((L, d["di"]), jnp.float32),
        "out_proj": _normal(ks[2], (L, d["di"], d["D"]), d["di"]),
    }
    return {"embed": _normal(ks[5], (d["V"], d["D"]), d["D"]),
            "blocks": [{"norm1": jnp.ones((L, d["D"]), jnp.float32),
                        "mixer": mixer}],
            "rem": [], "final_norm": jnp.ones((d["D"],), jnp.float32)}


def make_params(seed: int, c: dict) -> dict:
    """float32 weights from the seed, made on the device in one call."""
    return _make_params(seed_key(seed), _freeze(c))


def _freeze(c: dict) -> tuple:
    keys = ("d_model", "n_layer", "vocab_size", "d_state", "headdim",
            "expand", "ngroups", "d_conv", "chunk_size", "norm_eps")
    return tuple((k, c[k]) for k in keys)


# ---------------------------------------------------------------------------
# precision of the compute inputs
# ---------------------------------------------------------------------------

def exact(x):
    return x


def fp8(x):
    """float8_e4m3fn with one scale per tensor, straight through in the
    backward pass: the precision below bfloat16 (the control).  It is put
    wherever the program computes in bfloat16: the weights as each layer
    takes them, the inputs and outputs of every matmul, the scan's inputs
    and output, and the residual stream between layers."""
    s = jnp.max(jnp.abs(x)) / F8_MAX + 1e-30
    # clipped: e4m3fn has no infinity, and a quotient rounded past 448
    # would convert to NaN
    y = (jnp.clip(x / s, -F8_MAX, F8_MAX).astype(jnp.float8_e4m3fn)
         .astype(jnp.float32) * s)
    return x + jax.lax.stop_gradient(y - x)


PRECISIONS = {"float32": exact, "fp8": fp8}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _conv(x, w, b, q):
    """Causal depthwise conv: out_t = sum_k w[:, k] x_{t-(K-1)+k} + b."""
    K = w.shape[1]
    S = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, k:k + S, :] * w[:, k] for k in range(K))
    return q(out + b)


def _ssd(x, dt, A, Bm, Cm, Dskip, Q, q):
    """x (R,S,H,P), dt (R,S,H), A (H,), Bm/Cm (R,S,G,N) -> y (R,S,H,P)."""
    R, S, H, P = x.shape
    G = Bm.shape[2]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2).reshape(R, S // Q, Q, H, -1)
    Ch = jnp.repeat(Cm, rep, axis=2).reshape(R, S // Q, Q, H, -1)
    xc = x.reshape(R, S // Q, Q, H, P)
    dtc = dt.reshape(R, S // Q, Q, H)
    cum = jnp.cumsum(dtc * A, axis=2)                       # (R,c,Q,H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # (R,c,i,j,H)
    decay = jnp.exp(jnp.where(causal[None, None, :, :, None], seg, -jnp.inf))
    xdt = xc * dtc[..., None]
    scores = jnp.einsum("rcihn,rcjhn->rcijh", q(Ch), q(Bh), precision=HIGHEST)
    y_in = jnp.einsum("rcijh,rcjhp->rcihp", q(scores * decay), q(xdt),
                      precision=HIGHEST)
    # state at each chunk's end from its own inputs, then carried
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)               # (R,c,Q,H)
    chunk_state = jnp.einsum("rcjhn,rcjhp->rchpn", q(Bh),
                             q(xdt * to_end[..., None]), precision=HIGHEST)
    total = jnp.exp(cum[:, :, -1, :])                       # (R,c,H)

    def carry(state, inp):
        st, tot = inp
        return state * tot[..., None, None] + st, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((R, H, P, Bh.shape[-1]), jnp.float32),
        (jnp.moveaxis(chunk_state, 1, 0), jnp.moveaxis(total, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                 # (R,c,H,P,N)
    y_out = jnp.einsum("rcihn,rchpn->rcihp", q(Ch * jnp.exp(cum)[..., None]),
                       q(entering), precision=HIGHEST)
    y = (y_in + y_out).reshape(R, S, H, P)
    return y + x * Dskip[:, None]


def _layer(c, q, x, p):
    d = dims(c)
    R, S, _ = x.shape
    eps = c["norm_eps"]
    p = jax.tree.map(q, p)
    m = p["mixer"]
    h = _rms(x, p["norm1"], eps)
    zxbcdt = q(jnp.matmul(q(h), m["in_proj"], precision=HIGHEST))
    z, xBC, dt = jnp.split(zxbcdt, [d["di"], 2 * d["di"] + 2 * d["G"] * d["N"]],
                           axis=-1)
    xBC = jax.nn.silu(_conv(xBC, m["conv_w"], m["conv_b"], q))
    xs, Bm, Cm = jnp.split(xBC, [d["di"], d["di"] + d["G"] * d["N"]], axis=-1)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    A = -jnp.exp(m["A_log"])
    y = q(_ssd(q(xs).reshape(R, S, d["H"], d["P"]), dt, A,
               q(Bm).reshape(R, S, d["G"], d["N"]),
               q(Cm).reshape(R, S, d["G"], d["N"]), m["D"], d["Q"], q))
    y = _rms(y.reshape(R, S, d["di"]) * jax.nn.silu(z), m["norm"], eps)
    out = q(jnp.matmul(q(y), m["out_proj"], precision=HIGHEST))
    return q(x + out)


def loss(params, tokens, labels, c: dict, z_loss: float, q=exact):
    """Token-mean loss of rows ``tokens``/``labels`` (R, S)."""
    x = q(params["embed"])[tokens]
    layer = jax.checkpoint(functools.partial(_layer, c, q))
    x, _ = jax.lax.scan(lambda x, p: (layer(x, p), None), x,
                        params["blocks"][0])
    x = _rms(x, q(params["final_norm"]), c["norm_eps"])
    logits = q(jnp.matmul(q(x), q(params["embed"]).T, precision=HIGHEST))
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold + z_loss * lse * lse)


# ---------------------------------------------------------------------------
# training steps
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("frozen_c", "z_loss", "prec"),
                   donate_argnums=(0,))
def _accumulate(acc, params, tokens, labels, *, frozen_c, z_loss, prec):
    """acc + (loss, grad) of one block of rows, weighted by its rows."""
    c = dict(frozen_c)
    value, grad = jax.value_and_grad(loss)(params, tokens, labels, c, z_loss,
                                           PRECISIONS[prec])
    w = tokens.shape[0]
    return jax.tree.map(lambda a, g: a + w * g, acc, (value, grad))


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 2))
def _adamw(params, grads, state, lr, *, opt):
    o = dict(opt)
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, o["clip_norm"] / (gnorm + 1e-9))
    count = state["count"] + 1
    bc1 = 1.0 - o["b1"] ** count
    bc2 = 1.0 - o["b2"] ** count

    def upd(p, g, m, v, decays):
        g = g * scale
        m = o["b1"] * m + (1 - o["b1"]) * g
        v = o["b2"] * v + (1 - o["b2"]) * g * g
        step = (m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
        if decays:
            step = step + o["weight_decay"] * p
        return p - lr * step, m, v

    out = jax.tree_util.tree_map_with_path(
        lambda path, p, g, m, v: upd(p, g, m, v, _decays(path)),
        params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), {"m": pick(1), "v": pick(2), "count": count}


def _decays(path) -> bool:
    """Weight decay reaches the matrices only: the embedding, the
    projections and the conv taps, not norms, A_log, D or biases."""
    name = getattr(path[-1], "key", "")
    return name in ("embed", "in_proj", "out_proj", "conv_w")


def lr_at(step: int, opt: dict) -> float:
    """Linear warm-up, then cosine decay to a tenth (steps count from 0)."""
    peak, warm, total = opt["peak_lr"], opt["warmup"], opt["total_steps"]
    if step < warm:
        return peak * step / max(warm, 1)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * t)))


def train_steps(seed: int, c: dict, opt: dict, batches: list, *,
                prec: str = "float32", rows: int | None = None,
                block_rows: int = 1) -> dict:
    """Run ``len(batches)`` AdamW steps from the seeded weights.

    ``rows`` keeps the first rows of each batch only (a fault: part of the
    batch left out, the mean taken over the rest).  Returns the loss of
    each step, the clipped first gradient and the change of the weights
    after the last step, both as float32 host trees."""
    params = make_params(seed, c)
    p0 = jax.device_get(params)
    state = {"m": jax.tree.map(jnp.zeros_like, params),
             "v": jax.tree.map(jnp.zeros_like, params),
             "count": jnp.zeros((), jnp.int32)}
    fc = _freeze(c)
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        tokens, labels = batch["tokens"], batch["labels"]
        n = tokens.shape[0] if rows is None else rows
        acc = (jnp.zeros((), jnp.float32),
               jax.tree.map(jnp.zeros_like, params))
        for lo in range(0, n, block_rows):
            hi = min(n, lo + block_rows)
            acc = _accumulate(acc, params, jnp.asarray(tokens[lo:hi]),
                              jnp.asarray(labels[lo:hi]), frozen_c=fc,
                              z_loss=opt["z_loss"], prec=prec)
        value, grads = jax.tree.map(lambda a: a / n, acc)
        losses.append(float(value))
        params, state = _adamw(params, grads, state,
                               jnp.float32(lr_at(i, opt)),
                               opt=_freeze_opt(opt))
        if i == 0:
            # the gradient as the optimizer took it: m_1 / (1 - b1)
            first_grad = jax.device_get(
                jax.tree.map(lambda m: m / (1 - opt["b1"]), state["m"]))
        del grads, acc
    change = jax.tree.map(lambda a, b: np.asarray(a) - b,
                          jax.device_get(params), p0)
    return {"losses": losses, "first_grad": first_grad, "change": change}


def _freeze_opt(opt: dict) -> tuple:
    keys = ("b1", "b2", "eps", "weight_decay", "clip_norm")
    return tuple((k, float(opt[k])) for k in keys)
