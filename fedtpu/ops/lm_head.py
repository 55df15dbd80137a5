"""The head and its next-token loss, which never holds the logits whole.

The head and the cross-entropy run over chunks of the sequence: one chunk's
``[chunk, vocab]`` float32 logits exist at a time. The loss is a model's last
operation, so the function has its own differentiation rule
(``jax.custom_vjp``): the forward pass of a chunk takes the loss's gradient
from the logits it has and runs both gradient matmuls there; the backward
pass scales the result by the scalar cotangent. Three matmuls over the
vocabulary a chunk, where a checkpointed scan ran four. XLA's passes: a
kernel for the piece would stand here. The targets are the packed row's own
(``next_token_targets``: padding and each document's last token are out).

A model whose head IS its embedding (``tie_word_embeddings``) has one matrix
``E (V, H)`` for both. ``tied_lookup`` hands it out twice, the rows of a
sequence's tokens and the matrix for the head, and ``_tied_head_loss`` is
``_head_loss`` on ``E^T`` without the transpose ever made: the same three
products a chunk, contracted over the other axis, the head's gradient in the
embedding's own shape. In the backward pass the two gradients meet in
``tied_lookup``'s rule: the rows' cotangents are added INTO the head's
gradient at the tokens' rows, one scatter-add into an array that exists, so
the engine's accumulator sees one gradient for the one leaf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from fedtpu.ops.scopes import EMBED, TIED_EMBED_GRAD

# Rows of the sequence whose logits exist at one time in the loss.
LOSS_CHUNK = 512


def next_token_targets(tokens, segs):
    """``(labels (T,), valid (T,) float32)``: the next token where it belongs
    to the same document; padding and each document's last token are out."""
    labels = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
    nxt = jnp.concatenate([segs[1:], jnp.zeros((1,), segs.dtype)])
    return labels, ((segs > 0) & (nxt == segs)).astype(jnp.float32)


def _loss_chunks(h, labels, valid):
    """The head's inputs cut into ``LOSS_CHUNK`` rows, or left as one chunk
    where the sequence is no multiple of it."""
    t = h.shape[0]
    chunk = LOSS_CHUNK if t % LOSS_CHUNK == 0 else t
    return (h.reshape(-1, chunk, h.shape[1]), labels.reshape(-1, chunk),
            valid.reshape(-1, chunk))


def _loss_of_logits(logits, yc, vc):
    """A chunk's float32 ``(logits, log-sum-exp, summed loss, correct)``."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
    hit = (jnp.argmax(logits, axis=-1) == yc).astype(jnp.float32)
    return logits, lse, ((lse - picked) * vc).sum(), (hit * vc).sum()


def _chunk_loss(x, w, yc, vc):
    """One chunk's float32 ``(logits, log-sum-exp, summed loss, correct)``
    from ``x (chunk, H)`` and ``w (H, V)`` in the compute dtype."""
    return _loss_of_logits(
        jnp.dot(x, w, preferred_element_type=jnp.float32), yc, vc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _head_loss(h, head, labels, valid, compute_dtype):
    """``(summed loss, correct)`` over a sequence, a chunk of rows at a
    time. Called plainly (held-out evaluation) this is the forward pass
    alone. Differentiated, its own rule runs instead (``_head_loss_fwd``),
    reverse mode only: ``jax.jvp``, ``jacfwd`` and ``hessian`` through the
    head raise, and nothing in fedtpu uses them. ``labels`` and ``valid``
    are data (functions of the integer row): their cotangents are zero."""
    w = head.astype(compute_dtype)

    def one(carry, xs):
        hc, yc, vc = xs
        _, _, loss, correct = _chunk_loss(hc.astype(compute_dtype), w, yc, vc)
        return (carry[0] + loss, carry[1] + correct), None

    zero = jnp.float32(0.0)
    return lax.scan(one, (zero, zero), _loss_chunks(h, labels, valid))[0]


def _head_loss_fwd(h, head, labels, valid, compute_dtype):
    """The loss is the model's last operation and its cotangent one scalar,
    so each chunk's logits give, while they exist, the loss AND its gradient
    for a unit cotangent: ``dlogits = (softmax - onehot) * valid``,
    ``dh = dlogits w^T``, ``dw += h^T dlogits``. Three matmuls over the
    vocabulary a chunk and no recomputation; the backward rule only scales
    ``(dh, dw)``. ``dlogits`` enters its two matmuls in the compute dtype
    (what the MXU made of the float32 one autodiff handed it); ``dw`` is
    summed over the chunks in the compute dtype, as autodiff summed it,
    each chunk's float32 product added in float32 and rounded once."""
    w = head.astype(compute_dtype)

    def one(carry, xs):
        hc, yc, vc = xs
        loss, correct, dw = carry
        x = hc.astype(compute_dtype)
        logits, lse, chunk_loss, chunk_correct = _chunk_loss(x, w, yc, vc)
        onehot = yc[:, None] == jnp.arange(logits.shape[1])[None, :]
        dlogits = ((jnp.exp(logits - lse[:, None]) - onehot)
                   * vc[:, None]).astype(compute_dtype)
        dh = lax.dot_general(dlogits, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        dw = (dw.astype(jnp.float32) + lax.dot_general(
            x, dlogits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)).astype(dw.dtype)
        return (loss + chunk_loss, correct + chunk_correct, dw), dh

    zero = jnp.float32(0.0)
    (loss, correct, dw), dh = lax.scan(
        one, (zero, zero, jnp.zeros_like(w)), _loss_chunks(h, labels, valid))
    return (loss, correct), (dh.reshape(h.shape).astype(h.dtype), dw, head)


def _head_loss_bwd(compute_dtype, residuals, cotangents):
    dh, dw, head = residuals    # head: for its dtype, the parameters'
    g = cotangents[0]           # ``correct`` is a count: no gradient
    return ((g * dh).astype(dh.dtype),
            (g * dw.astype(jnp.float32)).astype(head.dtype), None, None)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


# ------------------------------------------------------------ a tied head
_OVER_HIDDEN = (((1,), (1,)), ((), ()))     # a (.., H) with b (.., H)
_OVER_ROWS = (((0,), (0,)), ((), ()))       # a (rows, ..) with b (rows, ..)


def _tied_chunk_loss(x, e, yc, vc):
    """``_chunk_loss`` with the head as ``e (V, H)``: ``logits = x e^T``."""
    return _loss_of_logits(lax.dot_general(
        x, e, _OVER_HIDDEN, preferred_element_type=jnp.float32), yc, vc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _tied_head_loss(h, embed, labels, valid, compute_dtype):
    """``_head_loss`` with ``embed (V, H)``, the embedding, as the head: the
    logits are ``h embed^T``. Forward alone where called plainly; its own
    rule where differentiated, reverse mode only."""
    e = embed.astype(compute_dtype)

    def one(carry, xs):
        hc, yc, vc = xs
        _, _, loss, correct = _tied_chunk_loss(hc.astype(compute_dtype), e,
                                               yc, vc)
        return (carry[0] + loss, carry[1] + correct), None

    zero = jnp.float32(0.0)
    return lax.scan(one, (zero, zero), _loss_chunks(h, labels, valid))[0]


def _tied_head_loss_fwd(h, embed, labels, valid, compute_dtype):
    """``_head_loss_fwd`` over the other axis: ``dh = dlogits e``, ``de +=
    dlogits^T x``, in the embedding's own shape ``(V, H)``."""
    e = embed.astype(compute_dtype)

    def one(carry, xs):
        hc, yc, vc = xs
        loss, correct, de = carry
        x = hc.astype(compute_dtype)
        logits, lse, chunk_loss, chunk_correct = _tied_chunk_loss(x, e, yc, vc)
        onehot = yc[:, None] == jnp.arange(logits.shape[1])[None, :]
        dlogits = ((jnp.exp(logits - lse[:, None]) - onehot)
                   * vc[:, None]).astype(compute_dtype)
        dh = jnp.dot(dlogits, e, preferred_element_type=jnp.float32)
        de = (de.astype(jnp.float32) + lax.dot_general(
            dlogits, x, _OVER_ROWS,
            preferred_element_type=jnp.float32)).astype(de.dtype)
        return (loss + chunk_loss, correct + chunk_correct, de), dh

    zero = jnp.float32(0.0)
    (loss, correct, de), dh = lax.scan(
        one, (zero, zero, jnp.zeros_like(e)), _loss_chunks(h, labels, valid))
    return (loss, correct), (dh.reshape(h.shape).astype(h.dtype), de, embed)


_tied_head_loss.defvjp(_tied_head_loss_fwd, _head_loss_bwd)


@jax.custom_vjp
def tied_lookup(embed, tokens):
    """``(embed[tokens], embed)``: the one matrix in its two places, the rows
    of a sequence's tokens and the head. Its rule is where the two gradients
    meet (reverse mode only)."""
    return jnp.take(embed, tokens, axis=0), embed


def _tied_lookup_fwd(embed, tokens):
    return tied_lookup(embed, tokens), tokens


def _tied_lookup_bwd(tokens, cotangents):
    d_rows, d_head = cotangents
    # the rows' cotangents into the head's gradient, which exists: no
    # second array of the embedding's size, and one gradient for the leaf
    with jax.named_scope(EMBED), jax.named_scope(TIED_EMBED_GRAD):
        return (d_head.at[tokens].add(d_rows.astype(d_head.dtype)), None)


tied_lookup.defvjp(_tied_lookup_fwd, _tied_lookup_bwd)
