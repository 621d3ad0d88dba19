"""Plain reference of the dense decoder the benchmark's configurations run.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``, written from the published description of a
pre-norm decoder and independent of the program under test (it imports
nothing from ``repro``):

    x_0     = E[tokens]
    h       = rmsnorm(x) * g_attn
    q, k, v = h Wq, h Wk, h Wv           (H query heads, KH key/value heads)
    q, k    = rope(q), rope(k)           (half-split pairs (i, i + hd/2))
    a       = softmax(q k^T / sqrt(hd) + causal mask) v   (head h reads
                                          key/value head h // (H / KH))
    x      += a Wo
    x      += (silu(h' Wgate) * (h' Wup)) Wdown,  h' = rmsnorm(x) * g_mlp
    logits  = (rmsnorm(x) * g_final) E^T  (tied) or  ... W_head (untied)

Training adds the mean token cross-entropy plus ``z_loss`` times the mean
squared log-partition, global-norm clipping, and AdamW with bias
correction and decoupled weight decay on the leaves that the cell's
traffic file names (``optimizer.decay``).  Parameters are stored in the
configuration's type (bfloat16) and every update is rounded to it,
because that storage is part of what the configuration states; all
arithmetic is float32.

``init_weights`` is the benchmark's own weight generator: one jitted call
from the seed, matrices in the configuration's type (bfloat16, the type
they are served in) and norm scales in float32.  The harness hands the
same tree to the program, and this reference makes it again from the seed
after the window.

``mode="fp8"`` is the control, the nearest precision below the
configuration's bfloat16: every matrix-product operand is rounded to
float8 e4m3 with one amax scale per tensor before the float32 product
(in training, of the forward and of the head's hand-written gradient).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                      # largest finite float8 e4m3fn
QUERY_BLOCK = 512                    # attention rows per block: bounds the
                                     # (H, block, S) score tile in float32


@dataclass(frozen=True)
class Spec:
    """The sizes of one configuration file (HF key names)."""
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    tied: bool
    rope_theta: float
    eps: float
    dtype: str = "bfloat16"           # the stored type of the matrices

    @classmethod
    def from_config(cls, c: dict) -> "Spec":
        return cls(d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   layers=c["num_hidden_layers"],
                   tied=bool(c["tie_word_embeddings"]),
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"])


def init_weights(spec: Spec, key) -> dict:
    """Seeded weights: normal matrices scaled by 1/sqrt(fan-in) in the
    stored type (bf16 for every benchmark configuration), embedding rows of
    standard deviation 0.02, norm scales 1 + 0.1 N(0, 1) in float32.  Layer
    leaves are stacked on a leading layer axis."""
    d, L, hd = spec.d, spec.layers, spec.head_dim
    qd, kvd = spec.heads * hd, spec.kv_heads * hd
    shapes = {"wq": (L, d, qd), "wk": (L, d, kvd), "wv": (L, d, kvd),
              "wo": (L, qd, d), "w_gate": (L, d, spec.ff),
              "w_up": (L, d, spec.ff), "w_down": (L, spec.ff, d)}
    keys = iter(jax.random.split(key, len(shapes) + 5))

    def mat(shape, std):
        return (jax.random.normal(next(keys), shape, F32) * std
                ).astype(spec.dtype)

    def scale(shape):
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, F32)

    layers = {n: mat(s, 1.0 / np.sqrt(s[1])) for n, s in shapes.items()}
    layers["attn_norm"] = scale((L, d))
    layers["mlp_norm"] = scale((L, d))
    w = {"embed": mat((spec.vocab, d), 0.02), "final_norm": scale((d,)),
         "layers": layers}
    if not spec.tied:
        w["head"] = mat((d, spec.vocab), 1.0 / np.sqrt(d))
    return w


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

@jax.custom_jvp
def fp8_round(x):
    """Round to float8 e4m3 with one amax scale for the whole tensor.  Its
    derivative is the identity (the straight-through rule), so gradients
    flow past the rounding in float32."""
    x = x.astype(F32)
    s = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s


@fp8_round.defjvp
def _fp8_round_jvp(primals, tangents):
    return fp8_round(primals[0]), tangents[0].astype(F32)


def mm(eq, a, b, mode):
    """A float32 matrix product at HIGHEST; under ``fp8`` both operands
    are rounded to float8 e4m3 first."""
    a, b = a.astype(F32), b.astype(F32)
    if mode == "fp8":
        a, b = fp8_round(a), fp8_round(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x: (B, S, heads, hd); pairs (i, i + hd/2) rotate by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None].astype(F32) * inv                     # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, mode):
    """Causal softmax attention in query blocks.  q: (B, S, H, hd);
    k, v: (B, S, KH, hd)."""
    S, H, hd = q.shape[1], q.shape[2], q.shape[3]
    group = H // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, S)
        s = mm("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi], mode) / np.sqrt(hd)
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(mm("bhqk,bkhd->bqhd", p, v[:, :hi], mode))
    return jnp.concatenate(out, axis=1)


def layer(spec, mode, x, lw):
    """One decoder layer on x: (B, S, d) float32."""
    B, S, _ = x.shape
    hd, pos = spec.head_dim, jnp.arange(S)
    h = rmsnorm(x, lw["attn_norm"], spec.eps)
    q = mm("bsd,dk->bsk", h, lw["wq"], mode).reshape(B, S, spec.heads, hd)
    k = mm("bsd,dk->bsk", h, lw["wk"], mode).reshape(B, S, spec.kv_heads, hd)
    v = mm("bsd,dk->bsk", h, lw["wv"], mode).reshape(B, S, spec.kv_heads, hd)
    a = attention(rope(q, pos, spec.rope_theta), rope(k, pos, spec.rope_theta),
                  v, mode).reshape(B, S, spec.heads * hd)
    x = x + mm("bsk,kd->bsd", a, lw["wo"], mode)
    h = rmsnorm(x, lw["mlp_norm"], spec.eps)
    u = (jax.nn.silu(mm("bsd,df->bsf", h, lw["w_gate"], mode))
         * mm("bsd,df->bsf", h, lw["w_up"], mode))
    return x + mm("bsf,fd->bsd", u, lw["w_down"], mode)


def hidden(spec, mode, w, tokens):
    """Final-normed hidden states (B, S, d) in float32."""
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)

    def body(x, lw):
        return layer(spec, mode, x, lw), None

    x, _ = jax.lax.scan(body, x, w["layers"])
    return rmsnorm(x, w["final_norm"], spec.eps)


def head_table(spec, w):
    """The output projection as a (V, d) table."""
    return w["embed"] if spec.tied else w["head"].T


@partial(jax.jit, static_argnums=(0, 1))
def gaps_and_top(spec, mode, w, tokens, picks):
    """Logits at the last ``n`` positions of each row of ``tokens`` (B, S),
    where ``picks`` (B, n) are the tokens chosen there: returns the gap
    (best logit - logit of the pick) and the argmax, both (B, n)."""
    n = picks.shape[1]
    h = hidden(spec, mode, w, tokens)[:, -n:]
    lg = mm("bnd,vd->bnv", h, head_table(spec, w), mode)
    picked = jnp.take_along_axis(lg, picks[..., None], axis=-1)[..., 0]
    return lg.max(-1) - picked, jnp.argmax(lg, -1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def _vocab_blocks(V: int, target: int = 8192) -> int:
    """The fewest equal blocks of at most ``target`` rows that tile V."""
    n = -(-V // target)
    while V % n:
        n += 1
    return n


def loss_and_grads(spec, mode, z_loss, w, batch):
    """Mean token cross-entropy plus z_loss * mean(logsumexp^2), and its
    gradient with respect to every leaf of ``w`` (float32).

    The head and the loss are differentiated by hand over vocabulary
    blocks, so no pass holds the whole (tokens, V) logits, and the
    embedding's gradient is built in one (V, d) buffer that the lookup's
    part is added into.  The layers are differentiated by ``jax.vjp``."""
    tokens, labels = batch["tokens"].reshape(-1), batch["labels"].reshape(-1)
    T, d = tokens.shape[0], spec.d
    x0 = jnp.take(w["embed"], tokens, axis=0).astype(F32).reshape(
        batch["tokens"].shape + (d,))

    def body(layers, final_norm, x):
        x, _ = jax.lax.scan(lambda x, lw: (layer(spec, mode, x, lw), None),
                            x, layers)
        return rmsnorm(x, final_norm, spec.eps).reshape(T, d)

    h, body_vjp = jax.vjp(body, w["layers"], w["final_norm"], x0)
    table = head_table(spec, w)
    nb = _vocab_blocks(spec.vocab)
    vb = spec.vocab // nb

    def blk(i):
        return jax.lax.dynamic_slice_in_dim(table, i * vb, vb).astype(F32)

    def lse_step(carry, i):
        m, s = carry
        lg = mm("td,vd->tv", h, blk(i), mode)
        m_new = jnp.maximum(m, lg.max(-1))
        return (m_new, s * jnp.exp(m - m_new)
                + jnp.exp(lg - m_new[:, None]).sum(-1)), None

    (m, s), _ = jax.lax.scan(
        lse_step, (jnp.full((T,), -jnp.inf, F32), jnp.zeros((T,), F32)),
        jnp.arange(nb))
    lse = m + jnp.log(s)
    rows = jnp.take(table, labels, axis=0).astype(F32)
    picked = mm("td,td->t", h, rows, mode)
    loss = jnp.mean(lse - picked) + z_loss * jnp.mean(lse * lse)

    # d loss / d logits[t, v] = (softmax[t, v] (1 + 2 z lse[t]) - [v = y_t]) / T
    dlse = (1.0 + 2.0 * z_loss * lse) / T

    def dlogits(b):
        return jnp.exp(mm("td,vd->tv", h, b, mode) - lse[:, None]) * dlse[:, None]

    def dh_step(dh, i):
        b = blk(i)
        return dh + mm("tv,vd->td", dlogits(b), b, mode), None

    def dtable_step(_, i):
        return None, mm("tv,td->vd", dlogits(blk(i)), h, mode)

    dh, _ = jax.lax.scan(dh_step, jnp.zeros((T, d), F32), jnp.arange(nb))
    _, dtable = jax.lax.scan(dtable_step, None, jnp.arange(nb))
    dtable = dtable.reshape(spec.vocab, d)
    dh = dh - rows / T
    dtable = dtable.at[labels].add(-h / T)
    dlayers, dfinal, dx0 = body_vjp(dh)
    g = {"layers": dlayers, "final_norm": dfinal}
    if spec.tied:
        g["embed"] = dtable.at[tokens].add(dx0.reshape(T, d))
    else:
        g["head"] = dtable.T
        g["embed"] = jnp.zeros((spec.vocab, d), F32).at[tokens].add(
            dx0.reshape(T, d))
    return loss, g


@partial(jax.jit, static_argnums=(0, 1, 2))
def grads(spec, mode, z_loss, w, batch):
    """(loss, float32 gradient tree) of ``loss_and_grads``."""
    return loss_and_grads(spec, mode, z_loss, w, batch)


@partial(jax.jit, donate_argnums=(1,))
def clip(max_norm, g):
    """``g`` scaled down to global norm ``max_norm`` where it is longer:
    the gradient as the optimizer gets it."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                         for x in jax.tree_util.tree_leaves(g)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-12))
    return jax.tree_util.tree_map(lambda x: x * scale, g)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
def adamw(decayed, hp, w, m, v, g, count):
    """One AdamW step with the clipped gradient ``g`` on ``w`` (kept in
    its stored types); returns (w, m, v).  ``hp``: (lr, b1, b2, eps,
    weight_decay); ``decayed``: the leaf names weight decay applies to."""
    lr, b1, b2, eps, wd = hp
    c = count.astype(F32)
    c1, c2 = 1 - b1 ** c, 1 - b2 ** c

    def upd(name, p, gg, mm_, vv):
        mm_ = b1 * mm_ + (1 - b1) * gg
        vv = b2 * vv + (1 - b2) * gg * gg
        step = (mm_ / c1) / (jnp.sqrt(vv / c2) + eps)
        if name in decayed:
            step = step + wd * p.astype(F32)
        return (p.astype(F32) - lr * step).astype(p.dtype), mm_, vv

    out = {}
    for name, p in w.items():
        if name == "layers":
            out[name] = {n: upd(n, p[n], g[name][n], m[name][n], v[name][n])
                         for n in p}
        else:
            out[name] = upd(name, p, g[name], m[name], v[name])
    pick = lambda i: {k: ({n: o[i] for n, o in x.items()} if k == "layers"
                          else x[i]) for k, x in out.items()}
    return pick(0), pick(1), pick(2)


def follow_training(spec, hp, decayed, make_weights, batches, mode="fp32"):
    """Run the reference over ``batches`` (a list of {"tokens", "labels"})
    from the weights ``make_weights()`` returns.  ``hp``: (lr, b1, b2,
    eps, weight_decay, max_grad_norm, z_loss).  Returns, on the host,
    {"losses": one per step, "grad": the first clipped gradient (float32),
    "params": the weights after the last step (stored types)}."""
    lr, b1, b2, eps, wd, max_norm, z_loss = hp
    w = make_weights()
    m = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), w)
    v = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, F32), w)
    losses, first = [], None
    for i, b in enumerate(batches):
        loss, g = grads(spec, mode, z_loss, w, b)
        g = clip(max_norm, g)
        if first is None:
            first = jax.device_get(g)
        w, m, v = adamw(tuple(sorted(decayed)), (lr, b1, b2, eps, wd),
                        w, m, v, g, jnp.int32(i + 1))
        del g
        losses.append(float(loss))
    del m, v
    return {"losses": losses, "grad": first, "params": jax.device_get(w)}
