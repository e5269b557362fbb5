"""Plain Mixtral reference: forward, loss, gradients and AdamW in float32.

Written from the published description (Mixtral of Experts, arXiv:2401.04088;
HeterMoE, arXiv:2504.03871) and the configuration file alone. It imports
nothing of the program under test and takes nothing the program made: it
draws its own weights from the seed, by the same documented recipe
(truncated normal at 2 sigma, std 1 / sqrt(fan_in), keys split in the
order the configuration file's ``init`` names), and is given only token
ids.

Semantics, and where they follow the configuration rather than Mixtral:

* pre-norm decoder: RMSNorm (eps 1e-6) -> GQA attention with rotary
  embeddings (half-split rotation, theta from the file) -> residual ->
  RMSNorm -> top-k MoE of SwiGLU experts -> residual; final RMSNorm and an
  untied LM head;
* router in float32: softmax over experts, top-k, weights renormalised
  over the k chosen;
* capacity: tokens are split into ``groups`` (the training job's
  microbatches, and within each its shards), each expert takes at most
  ``capacity`` assignments per group in token order (token-major, then
  choice order), and a dropped assignment adds nothing (GShard), the kept
  ones keeping their renormalised weights. Serving is dropless;
* loss: mean next-token cross entropy + z_loss_coef * mean(logsumexp^2),
  plus for every layer the Switch load-balance loss
  (E * sum_e f_e * p_e * router_aux_coef, f from the top-k counts before
  capacity) and the router z-loss (mean logsumexp^2 * router_z_coef), each
  averaged over the groups;
* AdamW with global-norm clipping, bias correction, linear warm-up and
  decoupled weight decay on every stored leaf of two or more dimensions.
  The configuration stores each layer's weights stacked over layers, so a
  layer's norm scale ([layers, d]) is decayed and the final norm's ([d])
  is not; the reference follows that.

``dot`` is the one matmul: float32 at ``highest`` precision, or, for the
control, the same with both operands rounded to float8 (e4m3, per-tensor
scale) first, the precision below the bfloat16 the configuration computes
in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30


# ---------------------------------------------------------------------------
# Matmul precision
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


# Gradients pass the rounding straight through, so the control keeps no
# copies beyond the rounded operands the matmul saves anyway.
_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


def make_dot(precision: str):
    """``f32``: float32 operands at highest precision. ``fp8``: operands
    rounded to float8 e4m3 with a per-tensor scale, float32 accumulation."""
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda spec, a, b: jnp.einsum(spec, _fp8(a), _fp8(b),
                                             precision=HIGHEST)
    raise ValueError(precision)


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------

def _normal(key, shape, fan_in):
    std = 1.0 / math.sqrt(fan_in)
    z = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return z * std / 0.87962566103423978


def init_params(m: dict, seed: int):
    """Weights as the configuration's ``init`` recipe draws them, one tree
    per layer under ``layers``."""
    d, V, E = m["d_model"], m["vocab_size"], m["n_experts"]
    H, KH, f = m["n_heads"], m["n_kv_heads"], m["d_ff_expert"]
    hd = d // H
    L = m["n_layers"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)

    def layer(key):
        k_attn, _, k_ffn, _ = jax.random.split(key, 4)
        k1, k2, k3, k4 = jax.random.split(k_attn, 4)
        r0, r1, r2, r3 = jax.random.split(k_ffn, 4)

        def experts(k, shape, fan_in):
            return jax.vmap(lambda kk: _normal(kk, shape, fan_in))(
                jax.random.split(k, E))
        return {
            "norm1": {"scale": jnp.ones((d,), jnp.float32)},
            "mixer": {"wq": _normal(k1, (d, H * hd), d),
                      "wk": _normal(k2, (d, KH * hd), d),
                      "wv": _normal(k3, (d, KH * hd), d),
                      "wo": _normal(k4, (H * hd, d), H * hd)},
            "norm2": {"scale": jnp.ones((d,), jnp.float32)},
            "ffn": {"router": _normal(r0, (d, E), d),
                    "wi_gate": experts(r1, (d, f), d),
                    "wi_up": experts(r2, (d, f), d),
                    "wo": experts(r3, (f, d), f)},
        }

    layer_keys = jax.random.split(jax.random.fold_in(ks[1], 0), L)
    stacked = jax.vmap(layer)(layer_keys)
    return {
        "embed": {"table": _normal(ks[0], (V, d), d)},
        "layers": [jax.tree.map(lambda x: x[i], stacked) for i in range(L)],
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "lm_head": _normal(ks[3], (V, d), d),
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x: [B, S, heads, hd]; pos: [S]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, m, u, dot, q_block=512):
    """Causal GQA over u [B, S, d], queries in blocks of ``q_block``."""
    B, S, d = u.shape
    H, KH = m["n_heads"], m["n_kv_heads"]
    hd = d // H
    G = H // KH
    pos = jnp.arange(S)
    q = rope(dot("bsd,dk->bsk", u, p["wq"]).reshape(B, S, H, hd), pos,
             m["rope_theta"])
    k = rope(dot("bsd,dk->bsk", u, p["wk"]).reshape(B, S, KH, hd), pos,
             m["rope_theta"])
    v = dot("bsd,dk->bsk", u, p["wv"]).reshape(B, S, KH, hd)
    qb = min(q_block, S)
    nb = -(-S // qb)
    pad = nb * qb - S
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qs = jnp.moveaxis(qp.reshape(B, nb, qb, KH, G, hd), 1, 0)

    @jax.checkpoint
    def block(args):
        qc, i = args
        s = dot("bqkgh,btkh->bkgqt", qc, k) / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, NEG)
        w = jax.nn.softmax(s, axis=-1)
        return dot("bkgqt,btkh->bqkgh", w, v)

    o = jax.lax.map(block, (qs, jnp.arange(nb)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, nb * qb, H * hd)[:, :S]
    return dot("bsk,kd->bsd", o, p["wo"])


def route(router_w, m, x):
    """x: [T, d] -> (weights [T, k], idx [T, k], probs [T, E], lse [T])."""
    logits = jnp.einsum("td,de->te", x, router_w, precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m["top_k"])
    return (w / jnp.sum(w, -1, keepdims=True), idx, probs,
            jax.nn.logsumexp(logits, -1))


def swiglu(p, x, dot, spec_in, spec_out):
    g = dot(spec_in, x, p["wi_gate"])
    u = dot(spec_in, x, p["wi_up"])
    return dot(spec_out, jax.nn.silu(g) * u, p["wo"])


def moe_capacity(p, m, x, capacity, dot):
    """One capacity group x [T, d]: each assignment goes to row
    ``expert * capacity + rank`` of a [E * capacity, d] buffer, where rank
    counts the earlier assignments to that expert in token order; an
    assignment of rank >= capacity is dropped (adds nothing)."""
    E, k = m["n_experts"], m["top_k"]
    T, d = x.shape
    w, idx, probs, lse = route(p["router"], m, x)
    flat = idx.reshape(-1)                                            # [Tk]
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, -1)
    keep = rank < capacity
    row = jnp.where(keep, flat * capacity + rank, E * capacity)
    tok = jnp.arange(T * k) // k
    buf = jnp.zeros((E * capacity + 1, d), x.dtype).at[row].set(x[tok])
    out = swiglu(p, buf[:-1].reshape(E, capacity, d), dot,
                 "ecd,edf->ecf", "ecf,efd->ecd").reshape(E * capacity, d)
    out = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)])
    y = jnp.sum((out[row] * (w.reshape(-1) * keep)[:, None])
                .reshape(T, k, d), axis=1)
    f = jnp.sum(onehot, 0) / (T * k)
    aux = E * jnp.sum(f * jnp.mean(probs, 0)) * m["router_aux_coef"]
    z = jnp.mean(lse ** 2) * m["router_z_coef"]
    return y, aux, z


def moe_dropless(p, m, x, dot, block=256):
    """x [T, d], every assignment kept: each expert's output weighted by
    its gate, over blocks of ``block`` tokens."""
    E = m["n_experts"]
    T = x.shape[0]
    nb = -(-T // block)
    xp = jnp.pad(x, ((0, nb * block - T), (0, 0))).reshape(nb, block, -1)

    def one(xb):
        w, idx, _, _ = route(p["router"], m, xb)
        gate = jnp.sum(jax.nn.one_hot(idx, E) * w[..., None], axis=1)  # [b,E]
        ye = swiglu(p, xb, dot, "td,edf->tef", "tef,efd->ted")
        return jnp.einsum("ted,te->td", ye, gate, precision=HIGHEST)

    return jax.lax.map(one, xp).reshape(nb * block, -1)[:T]


def layer_fwd(p, m, x, dot, groups, capacity):
    """One layer over x [B, S, d]. ``groups`` > 0: capacity groups of
    contiguous tokens; 0: dropless."""
    B, S, d = x.shape
    h = x + attention(p["mixer"], m, rms_norm(x, p["norm1"]["scale"]), dot)
    u = rms_norm(h, p["norm2"]["scale"]).reshape(B * S, d)
    if groups:
        ys, auxs, zs = jax.lax.map(
            lambda g: moe_capacity(p["ffn"], m, g, capacity, dot),
            u.reshape(groups, -1, d))
        y, aux, z = ys.reshape(B * S, d), jnp.mean(auxs), jnp.mean(zs)
    else:
        y, aux, z = moe_dropless(p["ffn"], m, u, dot), 0.0, 0.0
    return h + y.reshape(B, S, d), aux + z


def hidden(params, m, tokens, dot, groups=0, capacity=0):
    """Final-normed hidden states [B, S, d] and the summed router losses."""
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    layer = jax.checkpoint(
        lambda x, p: layer_fwd(p, m, x, dot, groups, capacity))
    aux = 0.0
    for p in params["layers"]:
        x, a = layer(x, p)
        aux = aux + a
    return rms_norm(x, params["final_norm"]["scale"]), aux


def lm_loss(params, m, tokens, targets, dot, groups, capacity,
            z_loss_coef=1e-4, chunk=512):
    h, aux = hidden(params, m, tokens, dot, groups, capacity)
    B, S, d = h.shape
    n = S // chunk if S % chunk == 0 else 1
    c = S // n
    hs = jnp.moveaxis(h.reshape(B, n, c, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(B, n, c), 1, 0)

    @jax.checkpoint
    def part(args):
        hc, tc = args
        logits = dot("bsd,vd->bsv", hc, params["lm_head"])
        lse = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, tc[..., None], -1)[..., 0]
        return jnp.sum(lse - gold), jnp.sum(lse ** 2)

    nll, zl = jax.lax.map(part, (hs, ts))
    return (jnp.sum(nll) + z_loss_coef * jnp.sum(zl)) / (B * S) + aux


def logits_at(params, m, tokens, dot):
    """Dropless forward of one sequence tokens [S] -> logits [S, V]."""
    h, _ = hidden(params, m, tokens[None], dot)
    return dot("sd,vd->sv", h[0], params["lm_head"])


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------

def lr_at(o: dict, step):
    """Linear warm-up to peak_lr over warmup_steps (steps are 1-based)."""
    return o["peak_lr"] * step / o["warmup_steps"]


def decays(params):
    """Which leaves take weight decay: those of two or more dimensions as
    the configuration stores them, where every layer leaf is stacked over
    the layers (so a layer's norm scale is decayed, the final norm's not)."""
    return {k: (jax.tree.map(lambda _: True, v) if k == "layers"
                else jax.tree.map(lambda x: x.ndim >= 2, v))
            for k, v in params.items()}


def adamw(o: dict, params, grads, mu, nu, step):
    """One AdamW step (``step`` 1-based); returns (params, mu, nu, per-leaf
    norms of the gradient as the moments took it: clipped)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["grad_clip"] / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = o["b1"], o["b2"]
    mu = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    lr = lr_at(o, step)

    def upd(p, m_, v, decay):
        delta = (m_ / (1 - b1 ** step)) / (
            jnp.sqrt(v / (1 - b2 ** step)) + o["eps"])
        if decay:
            delta = delta + o["weight_decay"] * p
        return p - lr * delta

    return (jax.tree.map(upd, params, mu, nu, decays(params)), mu, nu,
            leaf_norms(grads))


def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


def program_names(norms) -> dict:
    """Per-leaf norms keyed as the program stores its weights: a layer
    leaf's norms combine over the layers into the stacked leaf's norm."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(norms)[0]:
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        if keys[0] == "layers":
            name = "/".join(["blocks", "pos0"] + keys[2:])
            out[name] = out.get(name, 0.0) + float(v) ** 2
        else:
            out["/".join(keys)] = float(v) ** 2
    return {k: math.sqrt(v) for k, v in out.items()}


def program_arrays(tree) -> dict:
    """Host arrays keyed as the program stores its weights: a layer leaf's
    arrays stack over the layers into the stacked leaf."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        if keys[0] == "layers":
            out.setdefault("/".join(["blocks", "pos0"] + keys[2:]),
                           []).append(np.asarray(v))
        else:
            out["/".join(keys)] = np.asarray(v)
    return {k: np.stack(v) if isinstance(v, list) else v
            for k, v in out.items()}


def train_readings(m: dict, o: dict, seed: int, batches, groups: int,
                   capacity: int, precision: str = "f32"):
    """Drive the reference through ``len(batches)`` steps from the seed.

    Returns (losses per step, per-leaf norms of the first clipped gradient,
    per-leaf norms of the change of the weights over all steps, the first
    clipped gradient as host arrays); the norms as floats and the arrays
    keyed by the program's leaf names (``program_names``,
    ``program_arrays``). The gradient is read back from the first moment
    after one step over 1 - b1, as the program's is."""
    dot = make_dot(precision)
    loss_grad = jax.jit(jax.value_and_grad(functools.partial(
        lm_loss, m=m, dot=dot, groups=groups, capacity=capacity)))
    step_fn = jax.jit(functools.partial(adamw, o), donate_argnums=(0, 2, 3))
    diff_norms = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))
    init = jax.jit(functools.partial(init_params, m))
    seed = jnp.asarray(seed % 2**32, jnp.uint32)

    params = init(seed)
    # The moments wait on the host while the gradient is taken, so the
    # step needs only the weights, their gradient and its temporaries.
    moments, losses, g1, first = None, [], None, None
    for t, b in enumerate(batches, start=1):
        loss, grads = loss_grad(params, tokens=jnp.asarray(b["tokens"]),
                                targets=jnp.asarray(b["targets"]))
        losses.append(float(loss))
        if moments is None:
            mu = jax.tree.map(jnp.zeros_like, params)
            nu = jax.tree.map(jnp.zeros_like, params)
        else:
            mu, nu = jax.device_put(moments)
        params, mu, nu, gn = step_fn(params, grads, mu, nu,
                                     jnp.asarray(t, jnp.float32))
        del grads
        if t == 1 or t < len(batches):
            moments = jax.device_get((mu, nu))
        if t == 1:
            g1 = jax.device_get(gn)
            first = {k: v / (1 - o["b1"])
                     for k, v in program_arrays(moments[0]).items()}
        for x in jax.tree.leaves((mu, nu)):
            x.delete()
        del mu, nu
    change = jax.device_get(diff_norms(params, init(seed)))
    return losses, program_names(g1), program_names(change), first


def served_readings(m: dict, seed: int, max_len: int, n_rows: int, seqs,
                    control=None):
    """For each (prompt, served tokens, the program's logits at them): at
    every position that produced a served token, the reference's best
    logit minus its logit for that token, and the L2 distance of the
    program's logits from the reference's over the norm of the
    reference's. With ``control`` (a precision name), that precision's
    forward stands in the program's place: its logits, and the token it
    puts first. Returns one (gaps, distances) pair of arrays per sequence.

    Every sequence is padded to ``max_len`` at its end, which causal
    attention and the dropless MoE leave without effect on the positions
    compared, and its rows to ``n_rows``, so one program serves every
    length."""
    exact = make_dot("f32")
    low = make_dot(control) if control else None
    init = jax.jit(functools.partial(init_params, m))
    params = init(jnp.asarray(seed % 2**32, jnp.uint32))

    @jax.jit
    def read(params, toks, rows, ids, prog):
        logits = logits_at(params, m, toks, exact)[rows]
        if low is not None:
            prog = logits_at(params, m, toks, low)[rows]
            ids = jnp.argmax(prog, -1)
        pick = jnp.take_along_axis(logits, ids[:, None], -1)[:, 0]
        dist = (jnp.linalg.norm(prog - logits, axis=-1)
                / jnp.linalg.norm(logits, axis=-1))
        return jnp.max(logits, -1) - pick, dist

    out = []
    for prompt, served, prog in seqs:
        seq = list(prompt) + list(served[:-1])
        n = len(served)
        toks = np.zeros(max_len, np.int32)
        toks[:len(seq)] = seq
        rows = np.zeros(n_rows, np.int32)
        rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        ids = np.zeros(n_rows, np.int32)
        ids[:n] = served
        pad = np.zeros((n_rows, m["vocab_size"]), np.float32)
        if prog is not None:
            pad[:n] = prog
        gaps, dist = read(params, toks, rows, ids, pad)
        out.append((np.asarray(gaps)[:n], np.asarray(dist)[:n]))
    return out
