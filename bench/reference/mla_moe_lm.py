"""Plain reference of a DeepSeek-V3-architecture language model
(multi-head latent attention, routed experts under a sigmoid router with
a correction bias) and of the Phase III step that tunes it, in float32.

It imports nothing of the system under test.  It is written from the
published descriptions (DeepSeek-V2, arXiv:2405.04434 §2.1, for the
attention; DeepSeek-V3, arXiv:2412.19437 §2.1, for the router and its
balance term) and from the paper's Phase III (DeepFusion §IV.D): the
routed and shared expert FFNs and the router's correction bias are
frozen; embedding, attention, dense MLP, router, norms and head are
tuned with AdamW.  What carries over unchanged from the multi-head
attention reference comes from ``moe_lm``: RMSNorm, SwiGLU, rotary
embedding, ``tap``, ``unstack``, the storage rounding, ``change_norms``
and the float8 control.

The model, layer by layer:

    x = embed[tokens]
    per layer:  x += MLA(RMSNorm(x));  x += FFN(RMSNorm(x))
    logits = RMSNorm(x) @ lm_head

MLA, with a full-rank query (no ``q_lora_rank``):

    q = h W_q                      split per head into q_nope, q_rope
    c = h W_kva;  c_kv = RMSNorm(c[:r]);  k_rope = RoPE(c[r:])  (all heads)
    k_nope = c_kv W_kb;  v = c_kv W_vb                          (per head)
    score = (q_nope . k_nope + RoPE(q_rope) . k_rope) / sqrt(nope + rope)
    o = concat_h(softmax_causal(score) v) W_o

FFN is a SwiGLU MLP in the leading dense layers; in the others it is the
routed experts plus the shared experts.  The router scores each expert
with a sigmoid, picks each token's top-k by score plus the correction
bias among the ``topk_group`` groups (of ``n_group``) whose two best
biased scores sum highest, and weights each picked expert by its
unbiased score, renormalised to sum to one and then scaled.  The loss is
the mean next-token cross-entropy plus, per expert layer, the
sequence-wise balance term ``alpha * sum_i f_i P_i`` averaged over the
sequences.

Every float32 matrix product runs at ``Precision.HIGHEST``.  ``q``
rounds each matrix-product operand (the control: another precision).
"""
from __future__ import annotations

import functools
import math
import re
import zlib
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from reference import weights
from reference.moe_lm import (F32, HIGHEST, _ident, _layer_params, _mm,
                              _split, _tapped, change_norms, leaf_path,
                              rms_norm, rope, storage, swiglu, unstack)

# Phase III freezes the routed and shared expert FFNs, and the router's
# correction bias, which has no gradient and no update rule here
FROZEN = re.compile(
    r"moe/(wi_gate|wi_up|wo|e_score_correction_bias)$|moe/shared/")
BIAS = re.compile(r"moe/e_score_correction_bias$")
# the correction bias is made from the seed: a truncated normal of this
# standard deviation, about a tenth of the spread of the sigmoid scores,
# so it moves the choice where the scores are close and no further
BIAS_STD = 0.02
QUERY_ROWS = 1024    # query rows of one sequence whose scores are held
EXPERT_BLOCK = 8     # experts computed together over every token


def param_shapes(a: Dict) -> Dict:
    """path -> (shape, storage dtype) of every weight, stacked per layer
    group: ``dense_blocks`` (the leading dense layers) and ``blocks``
    (the expert layers).  The router and its bias are stored in float32,
    the rest in the configuration's dtype."""
    D, H, V, r = a["D"], a["H"], a["V"], a["r"]
    nope, rp, vd = a["nope"], a["rope"], a["v"]
    dt = a["dtype"]
    s = {"embed": ((V, D), dt), "final_norm/scale": ((D,), dt),
         "lm_head": ((D, V), dt)}

    def block(prefix, n, ffn):
        s[f"{prefix}/ln1/scale"] = ((n, D), dt)
        s[f"{prefix}/ln2/scale"] = ((n, D), dt)
        s[f"{prefix}/attn/wq"] = ((n, D, H * (nope + rp)), dt)
        s[f"{prefix}/attn/wkv_a"] = ((n, D, r + rp), dt)
        s[f"{prefix}/attn/kv_norm/scale"] = ((n, r), dt)
        s[f"{prefix}/attn/wk_b"] = ((n, H, r, nope), dt)
        s[f"{prefix}/attn/wv_b"] = ((n, H, r, vd), dt)
        s[f"{prefix}/attn/wo"] = ((n, H * vd, D), dt)
        for k, v in ffn.items():
            s[f"{prefix}/{k}"] = ((n,) + v[0], v[1])

    if a["n_dense"]:
        F = a["F_dense"]
        block("dense_blocks/sub0", a["n_dense"],
              {"mlp/wi_gate": ((D, F), dt), "mlp/wi_up": ((D, F), dt),
               "mlp/wo": ((F, D), dt)})
    E, F, Fs = a["E"], a["F"], a["F_shared"]
    ffn = {"moe/router": ((D, E), "float32"),
           "moe/e_score_correction_bias": ((E,), "float32"),
           "moe/wi_gate": ((E, D, F), dt), "moe/wi_up": ((E, D, F), dt),
           "moe/wo": ((E, F, D), dt)}
    if Fs:
        ffn.update({"moe/shared/wi_gate": ((D, Fs), dt),
                    "moe/shared/wi_up": ((D, Fs), dt),
                    "moe/shared/wo": ((Fs, D), dt)})
    block("blocks/sub0", a["n_moe"], ffn)
    return s


def frozen(path: str) -> bool:
    return bool(FROZEN.search(path))


def make(seed: int, shapes) -> Dict[str, jax.Array]:
    """Every leaf of ``shapes`` from the seed: ``weights.make``'s rules,
    and the correction bias as a truncated normal of ``BIAS_STD``."""
    out = weights.make(seed, {p: s for p, s in shapes.items()
                              if not BIAS.search(p)})
    key = weights.seed_key(seed)
    for p, (shape, dt) in shapes.items():
        if BIAS.search(p):
            k = jax.random.fold_in(key, zlib.crc32(p.encode()) & 0x7FFFFFFF)
            out[p] = (BIAS_STD * jax.random.truncated_normal(
                k, -2.0, 2.0, shape, F32)).astype(dt)
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def mla(p, x, a, q):
    B, S, _ = x.shape
    H, r, nope, rp, vd = a["H"], a["r"], a["nope"], a["rope"], a["v"]
    pos = jnp.arange(S)
    qh = _mm(x, p["wq"], q).reshape(B, S, H, nope + rp)
    q_nope = qh[..., :nope]
    q_rope = rope(qh[..., nope:], pos, a["theta"])
    c = _mm(x, p["wkv_a"], q)
    c_kv = rms_norm(c[..., :r], p["kv_norm"]["scale"], a["eps"])
    k_rope = rope(c[..., None, r:], pos, a["theta"])[:, :, 0]  # (B, S, rp)
    k_nope = jnp.einsum("bsr,hrn->bshn", q(c_kv), q(p["wk_b"]),
                        precision=HIGHEST)
    v = jnp.einsum("bsr,hrv->bshv", q(c_kv), q(p["wv_b"]),
                   precision=HIGHEST)
    rows = math.gcd(S, QUERY_ROWS)

    def one_seq(qn, qr, kn, kr, vs):
        @jax.checkpoint
        def query_rows(args):   # bounds the held scores to (H, rows, S)
            start, qn_b, qr_b = args
            s = (jnp.einsum("qhd,khd->hqk", q(qn_b), q(kn),
                            precision=HIGHEST)
                 + jnp.einsum("qhd,kd->hqk", q(qr_b), q(kr),
                              precision=HIGHEST)) / math.sqrt(nope + rp)
            causal = (start + jnp.arange(rows))[:, None] >= pos[None, :]
            pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", q(pr), q(vs),
                              precision=HIGHEST)

        n = S // rows
        o = jax.lax.map(query_rows, (jnp.arange(n) * rows,
                                     qn.reshape(n, rows, H, nope),
                                     qr.reshape(n, rows, H, rp)))
        return o.reshape(S, H * vd)

    o = jnp.stack([one_seq(q_nope[b], q_rope[b], k_nope[b], k_rope[b],
                           v[b]) for b in range(B)])
    return _mm(o, p["wo"], q)


def select(scores, bias, a):
    """(T, k) experts of each token: the top-k of score plus bias among
    the ``topk_group`` best groups, a group's worth being the sum of its
    two best biased scores."""
    T, E = scores.shape
    b = jax.lax.stop_gradient(scores + bias)
    G = a["n_group"]
    if G > 1:
        per = E // G
        grouped = b.reshape(T, G, per)
        worth = jnp.sum(jax.lax.top_k(grouped, min(2, per))[0], -1)
        _, best = jax.lax.top_k(worth, a["topk_group"])
        kept = jnp.sum(jax.nn.one_hot(best, G), 1) > 0        # (T, G)
        b = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(T, E)
    return jax.lax.top_k(b, a["k"])[1]


def moe(p, x, a, q, n_seq: int):
    """x (T, D) of ``n_seq`` sequences -> (out (T, D), balance term)."""
    E, k = a["E"], a["k"]
    T = x.shape[0]
    scores = jax.nn.sigmoid(_mm(x, p["router"], q))            # (T, E)
    idx = select(scores, _split(p["e_score_correction_bias"])[0], a)
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * a["scale"]
    onehot = jax.nn.one_hot(idx, E, dtype=F32)                 # (T, k, E)
    comb = jnp.einsum("tk,tke->te", w, onehot)                 # (T, E)
    # sequence-wise balance (DeepSeek-V3 eq. 17-20), per sequence of S
    S = T // n_seq
    f = E / (k * S) * jnp.sum(onehot.reshape(n_seq, S * k, E), 1)
    P = jnp.mean((scores / jnp.sum(scores, -1, keepdims=True)
                  ).reshape(n_seq, S, E), 1)
    aux = a["aux_coef"] * jnp.mean(jnp.sum(f * P, -1))

    # every expert over every token, EXPERT_BLOCK experts at a time,
    # weighted by its routing weight (0 where not chosen); recomputed in
    # the backward from its inputs, so nothing per block is kept but its
    # slice of the weights
    nb = math.gcd(E, EXPERT_BLOCK)

    @jax.checkpoint
    def ffn(x, wg, wu, wo, c):
        h = jax.nn.silu(jnp.einsum("td,edf->etf", q(x), q(wg),
                                   precision=HIGHEST))
        h = h * jnp.einsum("td,edf->etf", q(x), q(wu), precision=HIGHEST)
        y = jnp.einsum("etf,efd->etd", q(h), q(wo), precision=HIGHEST)
        return jnp.einsum("et,etd->td", c, y, precision=HIGHEST)

    ws, probes = zip(*(_split(p[n]) for n in ("wi_gate", "wi_up", "wo")))
    blocks = [w.reshape((E // nb, nb) + w.shape[1:]) for w in ws]

    def expert_block(acc, inp):
        *w, c = inp
        w = [_tapped(wi, pr) for wi, pr in zip(w, probes)]
        return acc + ffn(x, *w, c), None

    out, _ = jax.lax.scan(expert_block, jnp.zeros_like(x),
                          (*blocks, comb.T.reshape(E // nb, nb, T)))
    if "shared" in p:
        sw = [_tapped(*_split(p["shared"][n]))
              for n in ("wi_gate", "wi_up", "wo")]
        out = out + swiglu(x, *sw, q)
    return out, aux


def loss(params, tokens, labels, a, q: Callable = _ident):
    """Mean next-token cross-entropy plus the balance terms; ``params``
    as ``unstack`` gives them."""
    B, S = tokens.shape
    eps = a["eps"]
    x = params["embed"][tokens]
    aux = jnp.zeros((), F32)
    layers = ([("dense_blocks/sub0", i) for i in range(a["n_dense"])]
              + [("blocks/sub0", i) for i in range(a["n_moe"])])
    for prefix, i in layers:
        lp = _layer_params(params, prefix, i)

        @jax.checkpoint
        def block(x, lp=lp):
            x = x + mla(lp["attn"], rms_norm(x, lp["ln1"]["scale"], eps),
                        a, q)
            h = rms_norm(x, lp["ln2"]["scale"], eps)
            if "mlp" in lp:
                m = lp["mlp"]
                return x + swiglu(h, m["wi_gate"], m["wi_up"], m["wo"],
                                  q), jnp.zeros((), F32)
            y, au = moe(lp["moe"], h.reshape(B * S, -1), a, q, B)
            return x + y.reshape(B, S, -1), au

        x, au = block(x)
        aux = aux + au
    h = rms_norm(x, params["final_norm/scale"], eps).reshape(B * S, -1)
    lab = labels.reshape(-1)
    rows = math.gcd(h.shape[0], 1024)   # rows of logits at a time

    @jax.checkpoint
    def nll_rows(args):
        hr, lr = args
        z = _mm(hr, params["lm_head"], q)
        return jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, lr[:, None], -1)[:, 0]

    n = h.shape[0] // rows
    nll = jax.lax.map(nll_rows, (h.reshape(n, rows, -1), lab.reshape(n, rows)))
    return jnp.mean(nll) + aux


# ---------------------------------------------------------------------------
# Phase III step: AdamW with the global-norm clip of the system's optimizer,
# as ``moe_lm._step``, over this model's loss and frozen leaves
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("a", "q", "dtypes"),
                   donate_argnums=(0, 1, 2))
def _step(params, m, v, t, tokens, labels, hp, *, a, q, dtypes):
    """One step on ``unstack``'s leaves: trainable ones in float32 holding
    values of their storage dtype, to which the new weights are rounded;
    frozen ones in their storage dtype, returned as they came."""
    train = {k: x for k, x in params.items() if not frozen(leaf_path(k))}
    fixed = {k: x for k, x in params.items() if frozen(leaf_path(k))}

    def f(train, probes):
        p = dict(train, **{k: (fixed[k], probes[k]) for k in fixed})
        return loss(p, tokens, labels, dict(a), q)

    probes = {k: jnp.zeros((), F32) for k in fixed}
    lval, (g, gsq_fixed) = jax.value_and_grad(f, argnums=(0, 1))(train,
                                                                 probes)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values())
                     + sum(gsq_fixed.values()))
    scale = jnp.minimum(1.0, hp["clip"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = hp["b1"], hp["b2"]
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    dt = dict(dtypes)
    new_p, new_m, new_v, gsq = {}, {}, {}, {}
    for key, p in params.items():
        path = leaf_path(key)
        if frozen(path):
            new_p[key] = p
            continue
        gc = g[key] * scale
        gsq[path] = gsq.get(path, 0.0) + jnp.sum(gc * gc)
        new_m[key] = b1 * m[key] + (1 - b1) * gc
        new_v[key] = b2 * v[key] + (1 - b2) * gc * gc
        delta = (new_m[key] / c1) / (jnp.sqrt(new_v[key] / c2) + hp["eps"])
        delta = delta + hp["wd"] * p
        new_p[key] = storage(p - hp["lr"] * delta, dt[path])
    return new_p, new_m, new_v, lval, {k: jnp.sqrt(x) for k, x in gsq.items()}


def tune_readings(arch: Dict, make: Callable[[], Dict], batches, hp: Dict,
                  q: Callable = _ident) -> Dict:
    """Run len(batches) Phase III steps from the weights ``make()`` gives
    (path -> array in its storage dtype; ``make(keep)`` the leaves whose
    path ``keep`` accepts).  Returns the per-step losses, each trainable
    leaf's norm of the first clipped gradient, and each leaf's norm of
    the change over all the steps (nought for a frozen leaf)."""
    a = tuple(sorted(arch.items()))
    hp = {k: jnp.float32(x) for k, x in hp.items()}
    with jax.default_matmul_precision("highest"):
        made = make()
        dtypes = tuple(sorted((k, str(x.dtype)) for k, x in made.items()))
        params = unstack({k: x if frozen(k) else x.astype(F32)
                          for k, x in made.items()})
        del made
        m = {k: jnp.zeros(x.shape, F32) for k, x in params.items()
             if not frozen(leaf_path(k))}
        v = {k: jnp.zeros(x.shape, F32) for k, x in params.items()
             if not frozen(leaf_path(k))}
        losses, g1 = [], None
        for t, (tok, lab) in enumerate(batches, start=1):
            params, m, v, lv, gn = _step(params, m, v, jnp.float32(t),
                                         tok, lab, hp, a=a, q=q,
                                         dtypes=dtypes)
            losses.append(float(lv))
            if g1 is None:
                g1 = {k: float(x) for k, x in gn.items()}
        del m, v
        moved = {k: x for k, x in params.items() if not frozen(leaf_path(k))}
        p0 = unstack(make(lambda path: not frozen(path)))
        sq = {leaf_path(k): 0.0 for k in params}
        for k, x in change_norms(moved, p0).items():
            sq[leaf_path(k)] += float(x) ** 2
    return {"loss": losses, "grad": g1,
            "change": {k: x ** 0.5 for k, x in sq.items()}}
