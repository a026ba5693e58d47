"""Plain reference of a decoder-only language model with routed experts,
and of the Phase III step that tunes it, in float32.

It imports nothing of the system under test.  It is written from the
published descriptions of Qwen1.5-MoE and DeepSeek-MoE and from the
paper's Phase III (DeepFusion §IV.D): the routed and shared expert FFNs
are frozen; embedding, attention, router, norms and head are tuned with
AdamW.  Each departure from the published models that the system under
test makes, and that this reference therefore makes too, is named in
the configuration file under ``departures``.

The model, layer by layer:

    x = embed[tokens]
    per layer:  x += Attn(RMSNorm(x));  x += FFN(RMSNorm(x))
    logits = RMSNorm(x) @ lm_head

Attention is causal multi-head attention with rotary embeddings on the
two halves of each head.  FFN is a SwiGLU MLP in the leading dense
layers; in the others it is the routed experts plus the shared experts:
a softmax router picks the top-k experts of each token, their weights
are renormalised to sum to one, and every expert adds its output
times that weight.  The loss is the mean next-token cross-entropy plus,
per expert layer, the load-balance term ``coef * E * sum_e f_e p_e``.

Every float32 matrix product runs at ``Precision.HIGHEST``.  ``q``
rounds each matrix-product operand (the control: another precision).
"""
from __future__ import annotations

import functools
import math
import re
from typing import Callable, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
# the paper's Phase III freezes the routed and the shared expert FFNs
FROZEN = re.compile(r"moe/(wi_gate|wi_up|wo)$|moe/shared/")


def _ident(x):
    return x


def param_shapes(a: Dict) -> Dict:
    """path -> (shape, storage dtype) of every weight, stacked per layer
    group: ``dense_blocks`` (the leading dense layers) and ``blocks``
    (the expert layers).  The router is stored in float32, the rest in
    the configuration's dtype."""
    D, H, KH, Dh, V = a["D"], a["H"], a["KH"], a["Dh"], a["V"]
    dt = a["dtype"]
    s = {"embed": ((V, D), dt), "final_norm/scale": ((D,), dt),
         "lm_head": ((D, V), dt)}

    def block(prefix, n, ffn):
        s[f"{prefix}/ln1/scale"] = ((n, D), dt)
        s[f"{prefix}/ln2/scale"] = ((n, D), dt)
        s[f"{prefix}/attn/wq"] = ((n, D, H * Dh), dt)
        s[f"{prefix}/attn/wk"] = ((n, D, KH * Dh), dt)
        s[f"{prefix}/attn/wv"] = ((n, D, KH * Dh), dt)
        s[f"{prefix}/attn/wo"] = ((n, H * Dh, D), dt)
        for k, v in ffn.items():
            s[f"{prefix}/{k}"] = ((n,) + v[0], v[1])

    if a["n_dense"]:
        F = a["F_dense"]
        block("dense_blocks/sub0", a["n_dense"],
              {"mlp/wi_gate": ((D, F), dt), "mlp/wi_up": ((D, F), dt),
               "mlp/wo": ((F, D), dt)})
    E, F, Fs = a["E"], a["F"], a["F_shared"]
    ffn = {"moe/router": ((D, E), "float32"),
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


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _mm(x, w, q):
    return jnp.matmul(q(x), q(w), precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x (B, S, H, Dh): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions[:, None].astype(F32) * freqs[None, :]     # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, a, q):
    B, S, D = x.shape
    H, KH, Dh = a["H"], a["KH"], a["Dh"]
    pos = jnp.arange(S)
    qh = rope(_mm(x, p["wq"], q).reshape(B, S, H, Dh), pos, a["theta"])
    kh = rope(_mm(x, p["wk"], q).reshape(B, S, KH, Dh), pos, a["theta"])
    vh = _mm(x, p["wv"], q).reshape(B, S, KH, Dh)
    kh = jnp.repeat(kh, H // KH, axis=2)
    vh = jnp.repeat(vh, H // KH, axis=2)
    causal = pos[:, None] >= pos[None, :]

    @jax.checkpoint
    def one_row(args):    # one sequence at a time bounds the (H, S, S) scores
        qr, kr, vr = args
        s = jnp.einsum("qhd,khd->hqk", q(qr), q(kr),
                       precision=HIGHEST) / math.sqrt(Dh)
        pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", q(pr), q(vr), precision=HIGHEST)

    o = jax.lax.map(one_row, (qh, kh, vh))
    return _mm(o.reshape(B, S, H * Dh), p["wo"], q)


def swiglu(x, wg, wu, wo, q):
    return _mm(jax.nn.silu(_mm(x, wg, q)) * _mm(x, wu, q), wo, q)


@jax.custom_vjp
def tap(w, probe):
    """``w`` unchanged.  Its backward gives no gradient to ``w`` and the
    squared norm of ``w``'s cotangent to ``probe``: a frozen weight's
    gradient counts in the clip norm without ever being held whole."""
    return w


def _tap_fwd(w, probe):
    return w, None


def _tap_bwd(_, dw):
    return None, jnp.sum(jnp.square(dw))


tap.defvjp(_tap_fwd, _tap_bwd)


def _split(v):
    """A weight, or a frozen (weight, probe) pair -> (weight, probe)."""
    return v if isinstance(v, tuple) else (v, None)


def _tapped(w, probe):
    """A weight in float32; a frozen one, kept in its storage dtype, is
    widened here, one expert's slice at a time, before the tap, so its
    gradient is counted in float32."""
    w = w.astype(F32)
    return w if probe is None else tap(w, probe)


def moe(p, x, a, q):
    """x (T, D) -> (out (T, D), load-balance term)."""
    E, k = a["E"], a["k"]
    probs = jax.nn.softmax(_mm(x, p["router"], q), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.sum(w, -1, keepdims=True)
    onehot = jax.nn.one_hot(idx, E, dtype=F32)                 # (T, k, E)
    comb = jnp.einsum("tk,tke->te", w, onehot)                 # (T, E)
    aux = a["aux_coef"] * E * jnp.sum(jnp.mean(probs, 0)
                                      * jnp.mean(jnp.sum(onehot, 1), 0))

    # every expert over every token, weighted by its routing weight (0
    # where not chosen); recomputed in the backward from its inputs, so
    # nothing per expert is kept but its slice of the weights
    ffn = jax.checkpoint(lambda x, wg, wu, wo, c:
                         c[:, None] * swiglu(x, wg, wu, wo, q))

    ws, probes = zip(*(_split(p[k]) for k in ("wi_gate", "wi_up", "wo")))

    def expert(acc, inp):
        *w, c = inp
        w = [_tapped(wi, pr) for wi, pr in zip(w, probes)]
        return acc + ffn(x, *w, c), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), (*ws, comb.T))
    if "shared" in p:
        sw = [_tapped(*_split(p["shared"][k]))
              for k in ("wi_gate", "wi_up", "wo")]
        out = out + swiglu(x, *sw, q)
    return out, aux


STACKED = ("dense_blocks/sub0/", "blocks/sub0/")


def unstack(params: Dict) -> Dict:
    """Split each per-layer-group stacked leaf into one leaf per layer,
    ``<path>#<layer>``, so a layer's gradient can be freed once used."""
    out = {}
    for path, v in params.items():
        if path.startswith(STACKED):
            for i in range(v.shape[0]):
                out[f"{path}#{i}"] = v[i]
        else:
            out[path] = v
    return out


def leaf_path(key: str) -> str:
    return key.split("#")[0]


def _layer_params(params, prefix, i):
    out = {}
    for key, v in params.items():
        if key.startswith(prefix + "/") and key.endswith(f"#{i}"):
            node = out
            parts = leaf_path(key)[len(prefix) + 1:].split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = v
    return out


def loss(params, tokens, labels, a, q: Callable = _ident):
    """Mean next-token cross-entropy plus the load-balance terms;
    ``params`` as ``unstack`` gives them."""
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
            x = x + attention(lp["attn"], rms_norm(x, lp["ln1"]["scale"],
                                                   eps), a, q)
            h = rms_norm(x, lp["ln2"]["scale"], eps)
            if "mlp" in lp:
                m = lp["mlp"]
                return x + swiglu(h, m["wi_gate"], m["wi_up"], m["wo"],
                                  q), jnp.zeros((), F32)
            y, au = moe(lp["moe"], h.reshape(B * S, -1), a, q)
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
# Phase III step: AdamW with the global-norm clip of the system's optimizer
# ---------------------------------------------------------------------------

def storage(x, dtype: str):
    """``x`` rounded to the storage dtype, kept in float32.  A round trip
    through ``astype`` may be dropped by XLA (excess precision), so the
    rounding is spelled ``reduce_precision``, which it keeps."""
    fi = jnp.finfo(jnp.dtype(dtype))
    if fi.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


@functools.partial(jax.jit, static_argnames=("a", "q", "dtypes"),
                   donate_argnums=(0, 1, 2))
def _step(params, m, v, t, tokens, labels, hp, *, a, q, dtypes):
    """One step on ``unstack``'s leaves: trainable ones in float32 holding
    values of their storage dtype, to which the new weights are rounded
    as the configuration keeps them between steps; frozen ones in their
    storage dtype, returned as they came."""
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


@jax.jit
def change_norms(p, p0):
    """Per-leaf norm of p - p0, in float32."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(p[k].astype(F32)
                                           - p0[k].astype(F32))))
            for k in p}


def tune_readings(arch: Dict, make: Callable[[], Dict], batches, hp: Dict,
                  q: Callable = _ident) -> Dict:
    """Run len(batches) Phase III steps from the weights ``make()`` gives
    (path -> array in its storage dtype; ``make(keep)`` the leaves whose
    path ``keep`` accepts).  Returns the per-step losses, each trainable
    leaf's norm of the first clipped gradient, and each leaf's norm of
    the change over all the steps.  The first weights are made again at
    the end rather than held, which leaves the room."""
    a = tuple(sorted(arch.items()))
    hp = {k: jnp.float32(x) for k, x in hp.items()}
    with jax.default_matmul_precision("highest"):
        made = make()
        dtypes = tuple(sorted((k, str(x.dtype)) for k, x in made.items()))
        # frozen weights never change: they stay in their storage dtype,
        # which halves what the reference holds of the experts
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
        # a frozen leaf is returned as it came: its change is nought, and
        # only the trainable leaves' first weights are made again
        moved = {k: x for k, x in params.items() if not frozen(leaf_path(k))}
        p0 = unstack(make(lambda path: not frozen(path)))
        sq = {leaf_path(k): 0.0 for k in params}
        for k, x in change_norms(moved, p0).items():
            sq[leaf_path(k)] += float(x) ** 2
    return {"loss": losses, "grad": g1,
            "change": {k: x ** 0.5 for k, x in sq.items()}}


def fp8(x):
    """The control's precision: float8 e4m3 (4 exponent and 3 mantissa
    bits), one scale per tensor taking its largest magnitude to the
    largest finite value, on the forward operands of every product; the
    backward passes straight through."""
    top = 240.0   # (2 - 2**-3) * 2**7, the largest finite e4m3 value
    amax = jnp.max(jnp.abs(jax.lax.stop_gradient(x)))
    s = top / jnp.maximum(amax, 1e-30)
    r = jax.lax.reduce_precision(x * s, exponent_bits=4, mantissa_bits=3) / s
    return x + jax.lax.stop_gradient(r - x)



