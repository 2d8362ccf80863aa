"""The plain reference: the published decoder (Qwen2 / Qwen3) in f32.

Straight `jax.numpy` at ``precision="highest"``, with no kernels, caches,
batching or tiling of the program's: RMSNorm, per-head q/k RMSNorm
(Qwen3), q/k/v biases (Qwen2), rotary embeddings over the two halves of
each head, grouped-query causal softmax attention, a SwiGLU MLP, and the
untied lm_head over the real vocabulary.  It takes the parameter tree
that `bench/weights.py` makes and imports nothing of the program.

`compute` names the precision of the matrix multiplications' operands:
``"float32"`` for the reference; ``"float8_e4m3fn"`` rounds both operands
of every product to 8 bits in the forward pass (gradients flow through
the rounding unchanged), which is the control that must come out wrong.

Memory: the forward runs one sequence at a time, layer by layer under
`jax.checkpoint`, attention in blocks of queries and the loss in blocks of
rows, so the f32 model fits one chip beside its gradients.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024          # attention queries per block
ROW_BLOCK = 1024        # loss rows per block


def _round(x, compute: str):
    if compute == "float32":
        return x
    r = x.astype(jnp.dtype(compute)).astype(jnp.float32)
    return x + jax.lax.stop_gradient(r - x)


def _mm(eq, a, b, compute):
    return jnp.einsum(eq, _round(a, compute), _round(b, compute),
                      precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """x (T, n, hd): rotate the (first half, second half) pairs."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, compute):
    """Causal GQA: q (T, nq, hd), k/v (T, nkv, hd); query head i reads
    key/value head i // (nq // nkv)."""
    t, nq, hd = q.shape
    g = nq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(lo + Q_BLOCK, t)

        @jax.checkpoint
        def block(qb, k, v, lo=lo, hi=hi):
            s = _mm("qnh,knh->nqk", qb, k, compute) / math.sqrt(hd)
            qpos = jnp.arange(lo, hi)[:, None]
            kpos = jnp.arange(t)[None, :]
            s = jnp.where(kpos <= qpos, s, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1)
            return _mm("nqk,knh->qnh", a, v, compute)

        outs.append(block(q[lo:hi], k, v))
    return jnp.concatenate(outs, axis=0)


def _layer(x, p, spec, compute):
    t = x.shape[0]
    eps = spec["eps"]
    pos = jnp.arange(t)
    a = p["attn"]
    h = _rms(x, p["ln_attn"]["scale"], eps)
    q = _mm("td,dnh->tnh", h, a["wq"], compute)
    k = _mm("td,dnh->tnh", h, a["wk"], compute)
    v = _mm("td,dnh->tnh", h, a["wv"], compute)
    if spec["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    if spec["qk_norm"]:
        q = _rms(q, a["q_norm"], eps)
        k = _rms(k, a["k_norm"], eps)
    q = _rope(q, pos, spec["rope_theta"])
    k = _rope(k, pos, spec["rope_theta"])
    o = _attention(q, k, v, compute)
    x = x + _mm("tnh,nhd->td", o, a["wo"], compute)
    m = p["mlp"]
    h = _rms(x, p["ln_mlp"]["scale"], eps)
    up = _mm("td,df->tf", h, m["wi"], compute)
    gate = _mm("td,df->tf", h, m["wg"], compute)
    return x + _mm("tf,fd->td", jax.nn.silu(gate) * up, m["wo"], compute)


def hidden(params, tokens, spec, compute="float32"):
    """Final hidden states (T, d) of one sequence of token ids (T,)."""
    x = params["embed"]["table"][tokens]

    def body(x, p):
        return jax.checkpoint(
            lambda x, p: _layer(x, p, spec, compute))(x, p), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return _rms(x, params["ln_f"]["scale"], spec["eps"])


def logits(params, h, spec, compute="float32"):
    """(rows, vocab) logits over the real vocabulary."""
    w = params["lm_head"][:spec["vocab"]]
    return _mm("td,vd->tv", h, w, compute)


def sequence_loss_sum(params, tokens, targets, spec, compute="float32"):
    """Sum over the positions of one sequence of -log p(target)."""
    h = hidden(params, tokens, spec, compute)
    total = jnp.zeros((), jnp.float32)
    for lo in range(0, h.shape[0], ROW_BLOCK):
        hi = min(lo + ROW_BLOCK, h.shape[0])

        @jax.checkpoint
        def block(hb, yb):
            z = logits(params, hb, spec, compute)
            lse = jax.nn.logsumexp(z, axis=-1)
            return jnp.sum(lse - jnp.take_along_axis(z, yb[:, None], 1)[:, 0])

        total = total + block(h[lo:hi], targets[lo:hi])
    return total


# --------------------------------------------------------------------------
# training: three AdamW steps of the configured recipe
# --------------------------------------------------------------------------

def lr_at(step: int, recipe: dict) -> float:
    """The recipe's warmup + cosine learning rate at optimizer step `step`
    (0-based: the rate the (step+1)-th update uses)."""
    peak, warm, total = (recipe["peak_lr"], recipe["warmup_steps"],
                         recipe["total_steps"])
    if step < warm:
        return peak * step / max(warm, 1)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    frac = recipe.get("final_lr_frac", 0.1)
    return peak * (frac + (1 - frac) * 0.5 * (1 + math.cos(math.pi * t)))


def leaf_norms(tree) -> np.ndarray:
    """f32 Frobenius norm of every leaf, on the device, as one vector."""
    return np.asarray(jax.device_get(_leaf_norms(tree)), np.float64)


@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def _diff_norms(a, b):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


@jax.jit
def _near_share(tree, tol):
    """Every leaf's share of elements under `tol` in magnitude."""
    return jnp.stack([jnp.mean((jnp.abs(x) < tol).astype(jnp.float32))
                      for x in jax.tree.leaves(tree)])


def diff_norms(a, b) -> np.ndarray:
    return np.asarray(jax.device_get(_diff_norms(a, b)), np.float64)


def train_readings(params0, batches, spec, recipe, compute="float32",
                   steps=3, eps_in_root=True):
    """Follow `steps` AdamW updates from `params0` (any float dtype; the
    reference works on an f32 copy) over `batches` [(tokens (B, T),
    targets (B, T))], and return what the program's run is held to:
    the loss of each step, every leaf's norm of the first gradient as the
    optimizer receives it (after clipping), every leaf's norm of the
    change of the parameters over the steps, and every leaf's first
    gradient norm before clipping (for the rule that leaves out leaves
    whose gradient is nought to rounding).

    The update is AdamW's with eps inside the root, m^ / sqrt(v^ + eps^2)
    (`eps_in_root`), as the recipe's optimizer takes it; the textbook form
    m^ / (sqrt(v^) + eps) differs from it where |g| is near eps."""
    p = jax.tree.map(lambda x: x.astype(jnp.float32), params0)
    p0_host = [np.asarray(x) for x in jax.tree.leaves(params0)]
    grad_seq = jax.jit(jax.value_and_grad(
        functools.partial(sequence_loss_sum, spec=spec, compute=compute)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    scale_tree = jax.jit(lambda g, s: jax.tree.map(lambda x: x * s, g),
                         donate_argnums=(0,))
    b1, b2, eps, wd = (recipe["b1"], recipe["b2"], recipe["eps"],
                       recipe["weight_decay"])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam_leaf(p, m, v, g, lr, count, decay):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / (1 - b1 ** count)
        vh = v / (1 - b2 ** count)
        if eps_in_root:
            upd = mh * jax.lax.rsqrt(vh + eps * eps)
        else:
            upd = mh / (jnp.sqrt(vh) + eps)
        upd = upd + decay * wd * p
        return p - lr * upd, m, v

    treedef = jax.tree.structure(p)
    m_host = [np.zeros(x.shape, np.float32) for x in jax.tree.leaves(p)]
    v_host = [np.zeros(x.shape, np.float32) for x in jax.tree.leaves(p)]
    losses, first, first_raw = [], None, None
    for s in range(steps):
        toks, tgts = batches[s]
        n_tok = int(np.sum(tgts >= 0))
        acc, total = None, 0.0
        for r in range(toks.shape[0]):
            l, g = grad_seq(p, jnp.asarray(toks[r]), jnp.asarray(tgts[r]))
            total += float(l)
            acc = g if acc is None else add(acc, g)
        acc = scale_tree(acc, jnp.float32(1.0 / n_tok))
        losses.append(total / n_tok)
        norms = leaf_norms(acc)
        gnorm = float(np.sqrt(np.sum(norms ** 2)))
        clip = min(1.0, recipe["max_grad_norm"] / max(gnorm, 1e-12))
        acc = scale_tree(acc, jnp.float32(clip))
        if s == 0:
            first_raw = norms
            first = norms * clip
            near = np.asarray(jax.device_get(_near_share(acc, 10 * eps)))
        lr = jnp.float32(lr_at(s, recipe))
        leaves_p, leaves_g = jax.tree.leaves(p), jax.tree.leaves(acc)
        del acc
        new_p = []
        for i, (x, g) in enumerate(zip(leaves_p, leaves_g)):
            decay = jnp.float32(1.0 if x.ndim >= 2 else 0.0)
            x, m, v = adam_leaf(x, jnp.asarray(m_host[i]),
                                jnp.asarray(v_host[i]), g, lr,
                                jnp.float32(s + 1), decay)
            m_host[i], v_host[i] = np.asarray(m), np.asarray(v)
            new_p.append(x)
        del leaves_g
        p = jax.tree.unflatten(treedef, new_p)
    change = np.array([
        float(np.linalg.norm((np.asarray(x, np.float64)
                              - a.astype(np.float64)).ravel()))
        for x, a in zip(jax.tree.leaves(p), p0_host)])
    return {"losses": np.array(losses), "first_grad": first,
            "first_grad_raw": first_raw, "change": change,
            "near_eps_share": near}

