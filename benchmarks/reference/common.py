"""What both references share: causal softmax attention and the
next-token loss, in float32 under ``default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs in bf16 passes)."""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def causal_attention(q, k, v):
    """q, k, v: (b, s, heads, d) float32 -> (b, s, heads, d)."""
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def next_token_loss(logits, ids):
    """Mean cross entropy of position t predicting token t+1."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def highest(fn):
    """jit ``fn`` with every matmul at full float32 precision."""
    jitted = jax.jit(fn, static_argnums=(2,))

    def run(params, ids, hp):
        with jax.default_matmul_precision("highest"):
            return jitted(params, ids, hp)
    return run


def make_api(forward):
    """forward(params, ids, hp) -> the three entry points a cell uses."""
    def loss(params, ids, hp):
        return next_token_loss(forward(params, ids, hp), ids)

    def loss_and_grad_norm(params, ids, hp):
        value, grads = jax.value_and_grad(loss)(params, ids, hp)
        sq = sum(jnp.sum(jnp.square(g.astype(F32)))
                 for g in jax.tree.leaves(grads))
        return value, jnp.sqrt(sq)

    return highest(forward), highest(loss), highest(loss_and_grad_norm)
