"""Plain reference of the Mistral decoder, in float32 at full matmul
precision, written from the published architecture (Mistral-7B paper,
arXiv:2310.06825, and the Hugging Face ``MistralForCausalLM``): token
embedding, then per layer RMSNorm -> GQA attention with rotary position
embedding (``rotate_half`` form, inverse frequencies
``theta ** (-2i / head_dim)``) -> residual, RMSNorm -> SwiGLU MLP
``down(silu(gate(x)) * up(x))`` -> residual; a final RMSNorm and an untied
head.  No sliding window (the configurations state none), no cache
tricks, no kernels: a prompt is processed in fixed-size chunks of queries
against a float32 cache of every earlier key, which is plain causal
attention computed in blocks so that it fits.  A query may be given, per
layer, the set of fixed-size blocks ("pages") of earlier keys it attends;
it always attends itself.

Weights are made here from the run's seed, by the published
initialisation (``initializer_range``: a normal draw per parameter
tensor, one key per tensor in the order of the sorted parameter names,
rounded to the served dtype, norm scales one), so the comparison needs
nothing the program made.  ``int8=True`` is the control: every weight matrix rounded to int8
with one scale per output channel, then computed the same way.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def sizes(c: Dict) -> Dict[str, int]:
    return dict(L=c["num_hidden_layers"], d=c["hidden_size"],
                H=c["num_attention_heads"], KVH=c["num_key_value_heads"],
                hd=c["head_dim"], f=c["intermediate_size"],
                V=-(-c["vocab_size"] // 128) * 128)


def leaf_shapes(c: Dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """Parameter tensors in the order the seeded scheme draws them (the
    keys of the nested parameter tree, sorted)."""
    s = sizes(c)
    L, d, H, KVH, hd, f, V = (s[k] for k in ("L", "d", "H", "KVH", "hd",
                                            "f", "V"))
    return [("wk", (L, d, KVH, hd)), ("wo", (L, H, hd, d)),
            ("wq", (L, d, H, hd)), ("wv", (L, d, KVH, hd)),
            ("w_down", (L, f, d)), ("w_gate", (L, d, f)),
            ("w_up", (L, d, f)), ("norm1", (L, d)), ("norm2", (L, d)),
            ("embed", (V, d)), ("final_norm", (d,)), ("unembed", (d, V))]


_NORMS = ("norm1", "norm2", "final_norm")
# contraction axes of each weight as the forward pass uses it (axis 0 of
# every block tensor is the layer); the int8 scale is per remaining index
_CONTRACT = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2),
             "w_gate": (1,), "w_up": (1,), "w_down": (1,),
             "embed": (1,), "unembed": (0,)}


def make_weights(c: Dict, seed: int, int8: bool = False
                 ) -> Dict[str, jax.Array]:
    """The model's weights from ``seed`` on the default device: matrices
    normal with standard deviation ``initializer_range`` (the published
    initialisation), in the configuration's dtype as served; norm scales
    float32 ones.  ``int8`` stores each matrix as int8 with a float32
    scale per output channel (``<name>_scale``), which the forward pass
    multiplies back in."""
    shapes = leaf_shapes(c)
    dt = jnp.dtype(c["torch_dtype"])
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out: Dict[str, jax.Array] = {}
    for key, (name, shape) in zip(keys, shapes):
        if name in _NORMS:
            out[name] = jnp.ones(shape, F32)
            continue
        sd = c["initializer_range"]
        w = jax.jit(lambda k, shape=shape, sd=sd: (
            jax.random.normal(k, shape, F32) * sd).astype(dt))(key)
        if int8:
            ax = _CONTRACT[name]
            w, scale = jax.jit(lambda w, ax=ax: _int8(w, ax))(w)
            out[name + "_scale"] = scale
        out[name] = w
    return out


def _int8(w, axes):
    wf = w.astype(F32)
    amax = jnp.max(jnp.abs(wf), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _w(p: Dict, name: str, cols=None):
    """Weight ``name`` as float32 (``cols``: a slice of its last axis,
    taken before the cast so no whole float32 copy is made)."""
    w = p[name] if cols is None else p[name][..., cols]
    w = w.astype(F32)
    if name + "_scale" in p:
        s = p[name + "_scale"]
        w = w * (s if cols is None else s[..., cols])
    return w


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x: (C, heads, hd); ``rotate_half`` rotary embedding."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None].astype(F32) * inv                   # (C, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


_LAYER = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
          "norm1", "norm2")


def _chunk(p, c, kc, vc, tokens, pos0, pvis, page: int,
           n_mlp_blocks: int = 4):
    """Run one chunk of queries through every layer (a scan over the
    stacked layers); returns the final normed hidden states (C, d) and
    the caches holding the chunk's keys and values too.  At layer ``l``
    query ``i`` attends the keys at positions up to ``pos0 + i`` whose
    page (``position // page``) is marked in ``pvis[l, i]``, and itself."""
    s = sizes(c)
    H, KVH, hd, f = s["H"], s["KVH"], s["hd"], s["f"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    C = tokens.shape[0]
    pos = pos0 + jnp.arange(C)
    x = jnp.take(_w(p, "embed"), tokens, axis=0)            # (C, d)
    kpos = jnp.arange(kc.shape[1])[None, :]
    causal = kpos <= pos[:, None]                             # (C, Smax)
    own = kpos == pos[:, None]
    G = H // KVH
    fb = f // n_mlp_blocks

    def layer(x, xs):
        w, kl, vl, pv = xs
        mask = (causal & jnp.repeat(pv, page, axis=-1)) | own
        xn = _rms(x, eps) * w["norm1"]
        q = _rope(jnp.einsum("cd,dhk->chk", xn, _w(w, "wq"), precision=HI),
                  pos, theta)
        k = _rope(jnp.einsum("cd,dhk->chk", xn, _w(w, "wk"), precision=HI),
                  pos, theta)
        v = jnp.einsum("cd,dhk->chk", xn, _w(w, "wv"), precision=HI)
        kl = jax.lax.dynamic_update_slice_in_dim(kl, k, pos0, axis=0)
        vl = jax.lax.dynamic_update_slice_in_dim(vl, v, pos0, axis=0)
        sc = jnp.einsum("ckgh,skh->ckgs", q.reshape(C, KVH, G, hd), kl,
                        precision=HI) / math.sqrt(hd)
        sc = jnp.where(mask[:, None, None, :], sc, -jnp.inf)
        o = jnp.einsum("ckgs,skh->ckgh", jax.nn.softmax(sc, axis=-1), vl,
                       precision=HI).reshape(C, H, hd)
        x = x + jnp.einsum("chk,hkd->cd", o, _w(w, "wo"), precision=HI)
        xn = _rms(x, eps) * w["norm2"]
        for j in range(n_mlp_blocks):
            cols = slice(j * fb, (j + 1) * fb)
            h = jax.nn.silu(jnp.dot(xn, _w(w, "w_gate", cols), precision=HI)) \
                * jnp.dot(xn, _w(w, "w_up", cols), precision=HI)
            w_down = w["w_down"][cols].astype(F32)
            if "w_down_scale" in w:
                w_down = w_down * w["w_down_scale"]
            x = x + jnp.dot(h, w_down, precision=HI)
        return x, (kl, vl)

    w = {k: v for k, v in p.items() if k.split("_scale")[0] in _LAYER}
    x, (kc, vc) = jax.lax.scan(layer, x, (w, kc, vc, pvis))
    return _rms(x, eps) * p["final_norm"], kc, vc


def _score(p, h, lookup):
    """Per row of ``h``: the best logit, its token, and the logits of the
    ``lookup`` tokens (rows, k)."""
    lg = jnp.dot(h, _w(p, "unembed"), precision=HI)
    return (lg.max(axis=-1), lg.argmax(axis=-1).astype(jnp.int32),
            jnp.take_along_axis(lg, lookup, axis=-1))


class Reference:
    """Scores of one configuration at chosen positions of a token
    sequence.  ``chunk`` queries go through the layers at a time against
    a float32 cache of ``s_max`` keys, so one program serves every
    sequence length up to ``s_max``; ``page`` is the block size of the
    key sets a query may be given."""

    def __init__(self, c: Dict, seed: int, s_max: int, chunk: int = 512,
                 page: int = 64, int8: bool = False):
        self.c = c
        self.chunk = chunk
        self.page = page
        step = chunk * page // math.gcd(chunk, page)
        self.s_max = -(-s_max // step) * step
        self.n_pages = self.s_max // page
        self.p = make_weights(c, seed, int8=int8)
        s = sizes(c)
        self.cache_shape = (s["L"], self.s_max, s["KVH"], s["hd"])
        self._chunk = jax.jit(lambda p, kc, vc, t, pos0, pv: _chunk(
            p, c, kc, vc, t, pos0, pv, page), donate_argnums=(1, 2))
        self._score = jax.jit(_score)

    def score(self, tokens: Sequence[int], positions: Sequence[int],
              lookup: np.ndarray, pages: Optional[Dict[int, np.ndarray]] = None
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """For each of ``positions`` (ascending) of ``tokens``, the logits
        predicting the next token: returns (best logit, its token, logits
        of the ``lookup`` tokens (n, k)), all float32/int32 numpy.  A
        position attends every earlier key, unless ``pages`` maps its index
        in ``positions`` to an (L, n) boolean array of the pages each layer
        attends (pages past ``n`` are not attended)."""
        toks = np.asarray(tokens, np.int32)
        assert len(toks) <= self.s_max, (len(toks), self.s_max)
        C = self.chunk
        n = -(-len(toks) // C)
        padded = np.zeros(n * C, np.int32)
        padded[:len(toks)] = toks
        positions = np.asarray(positions)
        L = self.cache_shape[0]
        pvis = np.ones((n * C, L, self.n_pages), bool)
        for j, m in (pages or {}).items():
            pvis[positions[j]] = False
            pvis[positions[j], :, :m.shape[1]] = m
        lookup = np.asarray(lookup, np.int32).reshape(len(positions), -1)
        kc = jnp.zeros(self.cache_shape, F32)
        vc = jnp.zeros(self.cache_shape, F32)
        best = np.zeros(len(positions), np.float32)
        top = np.zeros(len(positions), np.int32)
        picked = np.zeros(lookup.shape, np.float32)
        for i in range(n):
            rows = slice(i * C, (i + 1) * C)
            h, kc, vc = self._chunk(
                self.p, kc, vc, jnp.asarray(padded[rows]), jnp.int32(i * C),
                jnp.asarray(pvis[rows].transpose(1, 0, 2)))
            sel = np.nonzero((positions >= i * C)
                             & (positions < (i + 1) * C))[0]
            if len(sel):
                b, t, pk = self._score(
                    self.p, h[jnp.asarray(positions[sel] - i * C)],
                    jnp.asarray(lookup[sel]))
                best[sel], top[sel], picked[sel] = \
                    np.asarray(b), np.asarray(t), np.asarray(pk)
        return best, top, picked

    def free(self) -> None:
        self.p = None
