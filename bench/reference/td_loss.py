"""DQN learner reference: the MinAtar-scale conv Q-network (one 3x3 VALID
convolution to 16 channels, ReLU, a dense layer of ``hidden`` units with
ReLU, a linear output per action; Young & Tian, arXiv:1903.03176), its
He initialisation from the run key, the importance-weighted TD loss and
Adam, written out in ``jax.numpy``.

The reference runs at ``Precision.HIGHEST`` in float32; the control runs
every array in bfloat16.  ``follow`` applies the learner's steps to the
same batches and returns each step's loss and TD errors, the parameters
after the last step and the first step's gradient.

A configuration names its learner reference in ``law.reference_learner``;
the check and ``learner_mfu`` reach it only through these three functions,
which every learner reference module defines, reading the sizes from the
configuration (``dqn``: ``history_len``, ``hidden``, ``gamma``,
``n_step``, ``lr``, ``batch``; ``law``: ``frame_hw``, ``n_actions``):

``learner_init(key, conf) -> params``
    the learner's initial weights from its run key;
``learner_follow(params, batches, weights, conf, *, step0, dtype)
-> (losses, td, params, grad0)``
    ``follow`` with the discount ``gamma ** n_step`` and the step size;
``update_flops(conf) -> int``
    FLOPs of one learner update of the network this module checks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench import work

CONV_CHANNELS, K = 16, 3


def init(key, in_channels: int, frame_hw, hidden: int, n_actions: int):
    """The learner's initial weights from its run key: key -> (k1, _);
    k1 -> (k_conv, k_dense); conv w ~ N(0, 2/fan_in); each dense layer
    takes the next key of k_dense's chain, w ~ N(0, 2/fan_in); biases 0."""
    k1, _ = jax.random.split(key)
    k_c, k_d = jax.random.split(k1)
    fan = K * K * in_channels
    conv = {"w": jax.random.normal(k_c, (K, K, in_channels, CONV_CHANNELS))
            * (2.0 / fan) ** 0.5, "b": jnp.zeros(CONV_CHANNELS)}
    h, w = frame_hw
    sizes = [(h - K + 1) * (w - K + 1) * CONV_CHANNELS, hidden, n_actions]
    dense = []
    for a, b in zip(sizes[:-1], sizes[1:]):
        k, k_d = jax.random.split(k_d)
        dense.append({"w": jax.random.normal(k, (a, b)) * (2.0 / a) ** 0.5,
                      "b": jnp.zeros(b)})
    return {"conv": conv, "dense": dense}


def q_values(params, x, precision):
    y = lax.conv_general_dilated(
        x, params["conv"]["w"], (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
    h = jax.nn.relu(y + params["conv"]["b"]).reshape(x.shape[0], -1)
    d0, d1 = params["dense"]
    h = jax.nn.relu(jnp.dot(h, d0["w"], precision=precision) + d0["b"])
    return jnp.dot(h, d1["w"], precision=precision) + d1["b"]


def _loss(params, target, batch, w, gamma_n, precision):
    q = q_values(params, batch["obs"], precision)
    qa = jnp.take_along_axis(q, batch["action"][:, None], 1)[:, 0]
    boot = q_values(target, batch["next_obs"], precision).max(-1)
    y = batch["reward"] + gamma_n * (1 - batch["terminated"]) * boot
    td = qa - lax.stop_gradient(y)
    return jnp.mean(w * td * td), td


def _adam(params, g, m, v, step, lr):
    b1, b2, eps = 0.9, 0.999, 1e-8
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    c = step + 1.0
    rate = lr * jnp.sqrt(1 - b2 ** c) / (1 - b1 ** c)
    params = jax.tree.map(lambda p, a, b: p - rate * a / (jnp.sqrt(b) + eps),
                          params, m, v)
    return params, m, v


def follow(params, batches, weights, *, gamma_n: float, lr: float,
           step0: int = 0, dtype=jnp.float32):
    """Apply ``len(weights)`` learner steps; batches' leaves lead with the
    step axis.  -> (losses [S], td [S, B], params, first gradient)."""
    hi = dtype == jnp.float32
    precision = lax.Precision.HIGHEST if hi else lax.Precision.DEFAULT
    cast = lambda t: jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, t)
    params, batches, weights = cast(params), cast(batches), cast(weights)
    target = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def step(params, m, v, batch, w, s):
        (loss, td), g = jax.value_and_grad(_loss, has_aux=True)(
            params, target, batch, w, jnp.asarray(gamma_n, dtype),
            precision)
        params, m, v = _adam(params, g, m, v, s.astype(dtype),
                             jnp.asarray(lr, dtype))
        return params, m, v, loss, td, g

    losses, tds, grad0 = [], [], None
    for s in range(weights.shape[0]):
        b = jax.tree.map(lambda x: x[s], batches)
        params, m, v, loss, td, g = step(params, m, v, b, weights[s],
                                         jnp.int32(step0 + s))
        losses.append(loss)
        tds.append(td)
        grad0 = g if grad0 is None else grad0
    to32 = lambda t: jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), t)
    return (jnp.stack(losses).astype(jnp.float32),
            jnp.stack(tds).astype(jnp.float32), to32(params), to32(grad0))


def learner_init(key, conf: dict):
    d, law = conf["dqn"], conf["law"]
    return init(key, d["history_len"], law["frame_hw"], d["hidden"],
                law["n_actions"])


def learner_follow(params, batches, weights, conf: dict, *, step0: int,
                   dtype=jnp.float32):
    d = conf["dqn"]
    return follow(params, batches, weights, gamma_n=d["gamma"] ** d["n_step"],
                  lr=d["lr"], step0=step0, dtype=dtype)


def update_flops(conf: dict) -> int:
    """The plain max target: no online forward on ``next_obs``."""
    d, law = conf["dqn"], conf["law"]
    h, w = law["frame_hw"]
    fwd = work.conv_qnet_forward_flops(h, w, d["history_len"],
                                       hidden=d["hidden"],
                                       n_actions=law["n_actions"])
    return work.qnet_update_flops(d["batch"], fwd, double=False)
