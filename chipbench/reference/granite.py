"""Plain float32 reference of the Granite 3.0 decoder, written from the
published architecture (arXiv:2408.00190 family; hf:ibm-granite/
granite-3.0-2b-base), with the departures the system under test makes and
that the configuration file lists under ``reduced``:

- no embedding, attention, residual or logits multipliers (the published
  model scales by 12, 1/64, 0.22 and 1/8; attention here scales by
  1/sqrt(head_dim));
- an untied output head;
- RMSNorm epsilon 1e-6.

Everything else is the published block: pre-norm RMSNorm, grouped-query
attention with rotary embeddings (rotate-half, theta 10,000) and a causal
mask, a SiLU-gated MLP, a final RMSNorm and a mean token cross-entropy.

Besides the model this module holds the benchmark's weights and data: the
seeded initial weights in the parameter layout the trained program takes
(``init_params``), the seeded token rows (``make_rows``), and a copy of the
order in which each replica draws its rows (``batch_rows``). It imports
nothing of the program.

Matrix products run at ``Precision.HIGHEST`` in float32. ``matmul="fp8"``
is the control: every matmul operand is rounded to float8 (e4m3 forward,
e5m2 for the cotangents, each scaled by its own absolute maximum) before
the float32 product. Roundings go through ``lax.reduce_precision``, which
XLA keeps; a cast to a narrow type and back it may drop.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    eps: float
    rope_theta: float
    dtype: str

    @classmethod
    def from_config(cls, doc: dict) -> "Dims":
        """The sizes of a ``chipbench/configs/*.json`` document."""
        d, h = doc["hidden_size"], doc["num_attention_heads"]
        return cls(vocab=doc["vocab_size"], d_model=d,
                   n_layers=doc["num_hidden_layers"], n_heads=h,
                   n_kv_heads=doc["num_key_value_heads"],
                   head_dim=doc.get("head_dim") or d // h,
                   d_ff=doc["intermediate_size"], eps=doc["rms_norm_eps"],
                   rope_theta=doc["rope_theta"], dtype=doc["torch_dtype"])


# ------------------------------------------------------------ weights


def init_params(dims: Dims, key):
    """Seeded weights in the trained program's layout: matrices normal
    with std 1/sqrt(fan_in) in the configuration's dtype, norm scales 1 in
    float32, layers stacked on a leading axis. Jit it: one call makes the
    whole tree on the device."""
    dt = jnp.dtype(dims.dtype)
    D, H, K, P, F, L, V = (dims.d_model, dims.n_heads, dims.n_kv_heads,
                           dims.head_dim, dims.d_ff, dims.n_layers,
                           dims.vocab)
    shapes = {  # name: (shape, fan_in)
        "wq": ((L, D, H, P), D), "wk": ((L, D, K, P), D),
        "wv": ((L, D, K, P), D), "wo": ((L, H, P, D), H * P),
        "w_gate": ((L, D, F), D), "w_up": ((L, D, F), D),
        "w_down": ((L, F, D), F), "embed": ((V, D), D), "head": ((D, V), D),
    }
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))

    def mat(name):
        shape, fan_in = shapes[name]
        return (jax.random.normal(keys[name], shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dt)

    ones = lambda *s: jnp.ones(s, jnp.float32)
    layer = {"ln1": {"scale": ones(L, D)}, "ln2": {"scale": ones(L, D)},
             "attn": {n: mat(n) for n in ("wq", "wk", "wv", "wo")},
             "mlp": {n: mat(n) for n in ("w_gate", "w_up", "w_down")}}
    return {"embed": mat("embed"), "head": mat("head"), "stack": [layer],
            "ln_f": {"scale": ones(D)}}


# --------------------------------------------------------------- data


def make_rows(key, n_rows: int, seq_len: int, vocab: int, fanout: int = 64):
    """(inputs, targets), each (n_rows, seq_len) int32: rows of a seeded
    first-order Markov chain in which every token has ``fanout``
    successors drawn uniformly; targets are the next tokens."""
    k_sup, k_first, k_walk = jax.random.split(key, 3)
    support = jax.random.randint(k_sup, (vocab, fanout), 0, vocab)
    first = jax.random.randint(k_first, (n_rows,), 0, vocab)
    picks = jax.random.randint(k_walk, (seq_len, n_rows), 0, fanout)

    def step(prev, pick):
        nxt = support[prev, pick]
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, picks)
    rows = jnp.concatenate([first[None], rest], axis=0).T
    return rows[:, :-1].astype(jnp.int32), rows[:, 1:].astype(jnp.int32)


def batch_rows(key, replica: int, step: int, n_rows: int, batch: int):
    """Row indices replica ``replica`` trains on at ``step``: one seeded
    permutation of the rows per (replica, epoch), read in order."""
    per_epoch = max(n_rows // batch, 1)
    epoch, pos = divmod(step, per_epoch)
    k = jax.random.fold_in(jax.random.fold_in(key, replica), epoch)
    perm = jax.random.permutation(k, n_rows)
    return perm[pos * batch:(pos + 1) * batch]


# ---------------------------------------------------------- precision


#: (exponent bits, mantissa bits, largest finite value) of the float8
#: formats, as ``lax.reduce_precision`` rounds them: a rounding XLA keeps,
#: where a cast there and back may be dropped as excess precision
E4M3 = (4, 3, 240.0)
E5M2 = (5, 2, 57344.0)


def _round_scaled(x, fmt):
    """Round to the float8 format ``fmt`` after scaling by the tensor's
    absolute maximum to the format's largest value; return float32."""
    exp, mant, big = fmt
    x = x.astype(jnp.float32)
    s = big / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return jax.lax.reduce_precision(x * s, exp, mant) / s


@jax.custom_vjp
def _fp8(x):
    return _round_scaled(x, E4M3)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, g: (_round_scaled(g, E5M2),))


def store(x, dtype):
    """``x`` rounded to ``dtype``'s precision, kept in float32."""
    fi = jnp.finfo(dtype)
    if fi.bits == 32:
        return x
    return jax.lax.reduce_precision(x, fi.nexp, fi.nmant)


def _mm(eq, a, b, matmul):
    if matmul == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HI)


# -------------------------------------------------------------- model


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, heads, P); rotate-half rotary embedding."""
    S, P = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(P // 2, dtype=jnp.float32) / (P // 2))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(dims: Dims, p, x, matmul):
    B, S, _ = x.shape
    G = dims.n_heads // dims.n_kv_heads
    h = _rms(x, p["ln1"]["scale"], dims.eps)
    q = _rope(_mm("bsd,dhp->bshp", h, p["attn"]["wq"], matmul),
              dims.rope_theta)
    k = _rope(_mm("bsd,dkp->bskp", h, p["attn"]["wk"], matmul),
              dims.rope_theta)
    v = _mm("bsd,dkp->bskp", h, p["attn"]["wv"], matmul)
    qg = q.reshape(B, S, dims.n_kv_heads, G, dims.head_dim)
    s = _mm("bskgp,btkp->bkgst", qg, k, matmul) / np.sqrt(dims.head_dim)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("bkgst,btkp->bskgp", a, v, matmul).reshape(
        B, S, dims.n_heads, dims.head_dim)
    x = x + _mm("bshp,hpd->bsd", o, p["attn"]["wo"], matmul)
    h = _rms(x, p["ln2"]["scale"], dims.eps)
    gate = _mm("bsd,df->bsf", h, p["mlp"]["w_gate"], matmul)
    up = _mm("bsd,df->bsf", h, p["mlp"]["w_up"], matmul)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up,
                   p["mlp"]["w_down"], matmul)


def loss(dims: Dims, params, tokens, targets, matmul: str = "f32"):
    """Mean token cross-entropy of float32 ``params`` on one batch."""
    x = params["embed"][tokens]
    stack = params["stack"][0]
    for i in range(dims.n_layers):
        x = _layer(dims, jax.tree.map(lambda a: a[i], stack), x, matmul)
    x = _rms(x, params["ln_f"]["scale"], dims.eps)
    logits = _mm("bsd,dv->bsv", x, params["head"], matmul)
    gold = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


@functools.partial(jax.jit, static_argnames=("dims", "matmul"))
def loss_and_grad(dims: Dims, params, tokens, targets, matmul="f32"):
    return jax.value_and_grad(loss, argnums=1)(dims, params, tokens,
                                               targets, matmul)


@functools.partial(jax.jit, static_argnames=("dtypes",),
                   donate_argnums=(0, 1))
def sgd_update(params, mu, grads, lr, momentum, weight_decay, dtypes):
    """Coupled weight decay and heavy-ball momentum in float32. Each new
    parameter is stored in the dtype the configuration states for it
    (``dtypes``, in leaf order: bfloat16 matrices, float32 norm scales),
    so an update smaller than half a unit in the last place of a
    bfloat16 weight is lost here as it is in a bfloat16 model."""
    g = jax.tree.map(lambda g, p: g + weight_decay * p, grads, params)
    mu = jax.tree.map(lambda m, g: momentum * m + g, mu, g)
    flat, tree = jax.tree.flatten(params)
    new = [store(p - lr * m, jnp.dtype(dt))
           for p, m, dt in zip(flat, jax.tree.leaves(mu), dtypes)]
    return jax.tree.unflatten(tree, new), mu


def leaf_norms(tree):
    """Per-leaf float32 L2 norms, flattened in tree order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def change_norms(new, old):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))])


def train_readings(dims: Dims, params0, batches, lrs, momentum: float,
                   weight_decay: float, matmul: str = "f32",
                   fault: str = ""):
    """The reference's readings over ``len(batches)`` SGD steps of one
    replica from ``params0`` (the configuration's dtype, upcast here).

    Returns (losses per step, per-leaf norms of the first step's
    momentum buffer — the gradient as the optimizer takes it — and
    per-leaf norms of the parameters' change over all the steps).
    ``fault="half_batch"`` plants a program fault the comparison has to
    catch: the loss is the mean over half of the rows."""
    dtypes = tuple(str(x.dtype) for x in jax.tree.leaves(params0))
    p = jax.tree.map(lambda x: jnp.array(x, jnp.float32, copy=True), params0)
    mu = jax.tree.map(jnp.zeros_like, p)
    losses, first = [], None
    for (tok, tgt), lr in zip(batches, lrs):
        if fault == "half_batch":
            tok, tgt = tok[: tok.shape[0] // 2], tgt[: tgt.shape[0] // 2]
        value, grads = loss_and_grad(dims, p, tok, tgt, matmul=matmul)
        losses.append(float(value))
        p, mu = sgd_update(p, mu, grads, jnp.float32(lr),
                           jnp.float32(momentum), jnp.float32(weight_decay),
                           dtypes)
        if first is None:
            first = np.asarray(jax.jit(leaf_norms)(mu))
    change = np.asarray(change_norms(p, params0))
    return np.asarray(losses), first, change
