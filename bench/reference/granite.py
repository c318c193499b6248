"""Plain float32 reference of the granite decoder mixture-of-experts.

Written from the architecture alone, in straightforward ``jax.numpy``;
it imports nothing of the program under test. It holds:

* ``dense_parent`` -- the dense parent checkpoint the benchmark makes
  from a seed (its own init, not the program's);
* ``upcycle`` -- sparse upcycling of that parent: every expert is a copy
  of the dense MLP (plus seeded noise where the configuration asks for
  it), routers are fresh normal(0, router_std) draws. The key chain is
  the one the upcycling surgery documents: layer ``l`` draws from
  ``fold_in(fold_in(key, 0), l)`` split three ways (router, noise,
  spare); expert matrix ``i`` in sorted name order draws its noise from
  ``fold_in(noise_key, i)``;
* ``logits`` -- the forward pass of one sequence, blocked over queries
  and experts so that 4096 tokens fit on one chip;
* ``loss`` -- next-token cross-entropy plus the load-balance loss of
  top-k routing, for a (batch, seq) block of tokens;
* ``adafactor_update`` -- the optimizer the fine-tune runs.

Every function takes ``dims``, a dict of sizes and settings (see
``bench/model.py``), and ``dtype``: float32 is the reference; bfloat16
is the lower-precision control. Callers set the matmul precision
(``jax.default_matmul_precision("highest")`` for the reference).

Parameter trees use the nested layout the program's checkpoints use
(``embed/tokens``, ``stack/segments/0/pos0/...``, ``final_norm/scale``),
so a leaf of one can be compared with the same leaf of the other.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


# -- weights ----------------------------------------------------------------

def _dense_shapes(dims):
    d, L, H, Kh, dh, f, V = (dims[k] for k in
                             ("d", "L", "H", "Kh", "dh", "f", "V"))
    # (shape, std) per leaf; std None means ones (a norm's scale).
    layer = {
        "pre_norm": {"scale": ((L, d), None)},
        "mixer": {
            "wq": ((L, d, H, dh), d ** -0.5),
            "wk": ((L, d, Kh, dh), d ** -0.5),
            "wv": ((L, d, Kh, dh), d ** -0.5),
            "wo": ((L, H, dh, d), (H * dh) ** -0.5),
        },
        "ffn_norm": {"scale": ((L, d), None)},
        "ffn": {
            "wi": ((L, d, f), d ** -0.5),
            "wg": ((L, d, f), d ** -0.5),
            "wo": ((L, f, d), f ** -0.5),
        },
    }
    return {
        "embed": {"tokens": ((V, d), 0.02)},
        "stack": {"segments": [{"pos0": layer}]},
        "final_norm": {"scale": ((d,), None)},
        "head": {},
    }


def dense_parent(key, dims):
    """The dense parent checkpoint: normal draws of 1/sqrt(fan-in)
    (0.02 for the embedding), leaf ``j`` in tree order from
    ``fold_in(key, j)``; norm scales are ones."""
    spec = _dense_shapes(dims)
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for j, (shape, std) in enumerate(leaves):
        if std is None:
            out.append(jnp.ones(shape, F32))
        else:
            out.append(std * jax.random.normal(jax.random.fold_in(key, j),
                                               shape, F32))
    return jax.tree.unflatten(treedef, out)


def upcycle(dense, key, dims):
    """Dense parent -> MoE tree: each MLP copied into ``E`` experts (with
    ``noise_std`` normal noise when ``expert_init`` is ``copy_noise``)
    and a fresh router per layer."""
    L, d, E = dims["L"], dims["d"], dims["E"]
    layer = dense["stack"]["segments"][0]["pos0"]
    ffn = layer["ffn"]
    lkeys = jax.vmap(lambda l: jax.random.fold_in(
        jax.random.fold_in(key, 0), l))(jnp.arange(L))
    split = jax.vmap(lambda k: jax.random.split(k, 3))(lkeys)  # (L, 3)
    kr, kn = split[:, 0], split[:, 1]
    router = jax.vmap(lambda k: dims["router_std"] * jax.random.normal(
        k, (d, E), F32))(kr)
    experts = {}
    for i, name in enumerate(sorted(ffn)):
        w = ffn[name]  # (L, a, b)
        tiled = jnp.broadcast_to(w[:, None], (L, E) + w.shape[1:])
        if dims["expert_init"] == "copy_noise":
            noise = jax.vmap(lambda k: jax.random.normal(
                jax.random.fold_in(k, i), (E,) + w.shape[1:], F32))(kn)
            tiled = tiled + dims["noise_std"] * noise
        elif dims["expert_init"] != "copy":
            raise ValueError(f"expert_init {dims['expert_init']!r}")
        experts[name] = tiled
    new_layer = dict(layer)
    new_layer["ffn"] = {"router": {"w": router}, "experts": experts}
    out = dict(dense)
    out["stack"] = {"segments": [{"pos0": new_layer}]}
    return out


# -- forward ------------------------------------------------------------------

def _rms(x, scale, eps):
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(x.dtype)


def _rope(x, theta):
    """Rotary embedding on (B, S, heads, dh), rotating the two halves of
    each head (the HF ``rotate_half`` convention)."""
    S, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq  # (S, half)
    cos = jnp.cos(ang)[None, :, None]
    sin = jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _attention(h, p, dims, q_block):
    """Causal grouped-query attention over (B, S, d), in query blocks so
    the (heads, q, S) scores of one block are all that is held."""
    B, S, _ = h.shape
    H, Kh, dh = dims["H"], dims["Kh"], dims["dh"]
    G = H // Kh
    q = _rope(jnp.einsum("bsd,dhk->bshk", h, p["wq"]), dims["theta"])
    k = _rope(jnp.einsum("bsd,dhk->bshk", h, p["wk"]), dims["theta"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    # query head j uses key/value head j // G
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    bq = min(q_block, S)
    nq = S // bq
    qb = q.reshape(B, nq, bq, H, dh).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = jnp.einsum("bqhk,bshk->bhqs", qi, k).astype(F32) * dh ** -0.5
        rows = i * bq + jnp.arange(bq)
        mask = rows[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqs,bshk->bqhk", pr, v)

    o = jax.lax.map(one, (jnp.arange(nq), qb))  # (nq, B, bq, H, dh)
    o = o.transpose(1, 0, 2, 3, 4).reshape(B, S, H, dh)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def _moe(x2, p, dims):
    """Top-k routing over all experts, each expert evaluated densely on
    every token and weighted by its combine weight (zero off the top k):
    the same sum a dropless dispatch computes. Returns (y, aux) with aux
    the load-balance term E * sum_e(top-1 share_e * mean prob_e),
    averaged over routing groups of ``group`` tokens."""
    N = x2.shape[0]
    E, k = dims["E"], dims["k"]
    logits = jnp.einsum("nd,de->ne", x2, p["router"]["w"],
                        preferred_element_type=F32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    comb = (jax.nn.one_hot(top_e, E, dtype=F32) * top_w[..., None]).sum(1)
    ex = p["experts"]

    @jax.checkpoint
    def body(y, xs):
        wi, wg, wo, c = xs
        h = jax.nn.silu(x2 @ wi) * (x2 @ wg)
        return y + (c[:, None] * (h @ wo).astype(F32)), None

    y, _ = jax.lax.scan(body, jnp.zeros(x2.shape, F32),
                        (ex["wi"], ex["wg"], ex["wo"], comb.T))
    g = min(dims["group"], N)
    Gn = N // g
    top1 = jax.nn.one_hot(top_e[:, 0], E, dtype=F32).reshape(Gn, g, E)
    pm = probs.reshape(Gn, g, E).mean(1)
    aux = E * jnp.mean(jnp.sum(top1.mean(1) * pm, -1))
    return y.astype(x2.dtype), aux


def _hidden(params, tokens, dims, dtype, q_block):
    """Final-norm hidden states (B, S, d) and the summed weighted
    load-balance loss, for tokens (B, S)."""
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
    layers = cast(params["stack"]["segments"][0]["pos0"])
    x = jnp.take(params["embed"]["tokens"].astype(dtype), tokens, axis=0)
    B, S, d = x.shape
    eps = dims["eps"]

    @jax.checkpoint
    def layer(x, lp):
        h = _rms(x, lp["pre_norm"]["scale"], eps)
        x = x + _attention(h, lp["mixer"], dims, q_block).astype(dtype)
        h = _rms(x, lp["ffn_norm"]["scale"], eps)
        y, aux = _moe(h.reshape(B * S, d), lp["ffn"], dims)
        return x + y.reshape(B, S, d), aux

    x, aux = jax.lax.scan(layer, x, layers)
    x = _rms(x, params["final_norm"]["scale"], eps)
    return x, dims["aux_weight"] * aux.sum()


def logits(params, tokens, dims, *, dtype=F32, q_block=512):
    """Next-token logits (S, V), float32, for one sequence ``tokens``
    (S,) of a length divisible by ``q_block`` (or shorter than it)."""
    x, _ = _hidden(params, tokens[None], dims, dtype, q_block)
    w = params["embed"]["tokens"].astype(dtype)
    return jnp.einsum("sd,vd->sv", x[0], w).astype(F32)


def loss(params, batch, dims, *, dtype=F32, q_block=512):
    """Mean next-token cross-entropy over ``batch["targets"]`` plus the
    weighted load-balance loss; the logits are formed one sequence at a
    time. Returns (loss, ce)."""
    x, aux = _hidden(params, batch["tokens"], dims, dtype, q_block)
    w = params["embed"]["tokens"].astype(dtype)

    @jax.checkpoint
    def seq_ce(args):
        xs, tg = args
        lg = jnp.einsum("sd,vd->sv", xs, w).astype(F32)
        lp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(lp, tg[:, None], axis=-1).sum()

    ce = jax.lax.map(seq_ce, (x, batch["targets"])).sum() / (
        batch["targets"].size)
    return ce + aux, ce


# -- optimizer ------------------------------------------------------------------

def adafactor_init(params):
    def slot(p):
        if p.ndim >= 2 and min(p.shape[-2:]) >= 128:
            return {"v_row": jnp.zeros(p.shape[:-1], F32),
                    "v_col": jnp.zeros(p.shape[:-2] + p.shape[-1:], F32)}
        return {"v": jnp.zeros(p.shape, F32)}

    return jax.tree.map(slot, params)


def adafactor_update(params, grads, state, lr, t):
    """One Adafactor step (Shazeer & Stern 2018) as the fine-tune runs
    it: second moments factored over the last two axes of a leaf whose
    last two sizes are both >= 128 (leading axes are batch axes), decay
    1 - t^-0.8, updates clipped to RMS 1 over the whole leaf, scaled by
    the leaf's parameter RMS (floor 1e-3), no momentum, no weight decay.
    ``t`` is the 1-based step number. Returns (new params, new
    state)."""
    beta2 = 1.0 - float(t) ** -0.8
    eps1 = 1e-30

    def one(p, g, s):
        g2 = g * g + eps1
        if "v_row" in s:
            vr = beta2 * s["v_row"] + (1 - beta2) * g2.mean(-1)
            vc = beta2 * s["v_col"] + (1 - beta2) * g2.mean(-2)
            r = vr / jnp.maximum(vr.mean(-1, keepdims=True), eps1)
            u = g / jnp.sqrt(r[..., None] * vc[..., None, :])
            new = {"v_row": vr, "v_col": vc}
        else:
            v = beta2 * s["v"] + (1 - beta2) * g2
            u = g / jnp.sqrt(v)
            new = {"v": v}
        u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(u * u) + 1e-30))
        scale = lr * jnp.maximum(jnp.sqrt(jnp.mean(p * p)), 1e-3)
        return p - scale * u, new

    pairs = jax.tree.map(one, params, grads, state)
    is_pair = lambda x: isinstance(x, tuple)
    new_p = jax.tree.map(lambda t_: t_[0], pairs, is_leaf=is_pair)
    slots = jax.tree.map(lambda t_: t_[1], pairs, is_leaf=is_pair)
    return new_p, slots
