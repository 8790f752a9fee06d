"""Plain float32 ``jax.numpy`` references: the IMPALA deep network and the
MLP forward, V-trace by its sequential recurrence, and the IMPALA loss.

Independent of the code under test: nothing here imports
``asyncrl_tpu.models``, ``ops`` or ``learn``. Parameters arrive as the
nested ``{"kernel", "bias"}`` dicts the program's flax modules use, named by
position (``Conv_0``, ``ResidualBlock_0`` ...), since the reference has to
read the weights the program trained. Every product runs at
``Precision.HIGHEST``: on a TPU a float32 matmul is otherwise computed in
bfloat16 passes.

Departures from Espeholt et al. 2018: none in the network (Fig. 3 right:
three sections of conv 3x3 / max-pool 3x3 stride 2 / two residual blocks,
16-32-32 channels, ReLU, FC 256, no LSTM). Pixels enter as raw 0..255
values, as the program feeds them. The loss sums nothing over time: it is
the mean over [T, B], as the program's is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _dense(p, x):
    return jnp.matmul(x, p["kernel"].astype(F32), precision=HIGHEST) + p[
        "bias"
    ].astype(F32)


def _conv3x3(p, x):
    y = jax.lax.conv_general_dilated(
        x, p["kernel"].astype(F32), window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )
    return y + p["bias"].astype(F32)


def _max_pool_3x3_s2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )


def _residual(p, x):
    y = _conv3x3(p["Conv_0"], jax.nn.relu(x))
    y = _conv3x3(p["Conv_1"], jax.nn.relu(y))
    return x + y


def _heads(params, h):
    logits = _dense(params["Dense_0"], h)
    value = _dense(params["Dense_1"], h)[..., 0]
    return logits, value


def impala_cnn_features(params, obs):
    """``obs`` [N, H, W, C] (any dtype) -> the torso's output [N, 256]."""
    torso = params["params"]["ImpalaCNN_0"]
    x = obs.astype(F32)
    sections = sum(1 for k in torso if k.startswith("Conv_"))
    for i in range(sections):
        x = _conv3x3(torso[f"Conv_{i}"], x)
        x = _max_pool_3x3_s2(x)
        x = _residual(torso[f"ResidualBlock_{2 * i}"], x)
        x = _residual(torso[f"ResidualBlock_{2 * i + 1}"], x)
    x = jax.nn.relu(x).reshape(x.shape[0], -1)
    return jax.nn.relu(_dense(torso["Dense_0"], x))


def impala_cnn_forward(params, obs):
    """-> (logits [N, A], value [N])."""
    return _heads(params["params"], impala_cnn_features(params, obs))


def mlp_features(params, obs):
    """tanh MLP torso over flattened observations."""
    torso = params["params"]["MLPTorso_0"]
    x = obs.astype(F32).reshape(obs.shape[0], -1)
    for i in range(len(torso)):
        x = jnp.tanh(_dense(torso[f"Dense_{i}"], x))
    return x


def mlp_forward(params, obs):
    return _heads(params["params"], mlp_features(params, obs))


FORWARDS = {"impala_cnn": impala_cnn_forward, "mlp": mlp_forward}
FEATURES = {"impala_cnn": impala_cnn_features, "mlp": mlp_features}


def forward_in_chunks(forward, params, obs, chunk: int):
    """Forward [N, ...] in chunks of ``chunk`` rows (N divisible), so the
    float32 activations of a real-size fragment fit beside the program."""
    n = obs.shape[0]
    if n <= chunk:
        return forward(params, obs)
    parts = obs.reshape(n // chunk, chunk, *obs.shape[1:])
    logits, values = jax.lax.map(lambda o: forward(params, o), parts)
    return logits.reshape(n, -1), values.reshape(n)


def log_softmax(logits):
    shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
    return shifted - jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))


def vtrace_sequential(behaviour_logp, target_logp, rewards, discounts,
                      values, bootstrap_value, rho_clip=1.0, c_clip=1.0):
    """Espeholt et al. 2018 eq. 1, by the backward recurrence one step at a
    time. All [T, B]; ``bootstrap_value`` [B]. Returns (vs, pg_advantages)."""
    rhos = jnp.exp(target_logp - behaviour_logp)
    clipped_rhos = jnp.minimum(rho_clip, rhos)
    cs = jnp.minimum(c_clip, rhos)
    values_tp1 = jnp.concatenate([values[1:], bootstrap_value[None]], axis=0)
    deltas = clipped_rhos * (rewards + discounts * values_tp1 - values)

    def back(acc, x):
        delta, discount, c = x
        acc = delta + discount * c * acc
        return acc, acc

    _, vs_minus_v = jax.lax.scan(
        back, jnp.zeros_like(bootstrap_value), (deltas, discounts, cs),
        reverse=True,
    )
    vs = vs_minus_v + values
    vs_tp1 = jnp.concatenate([vs[1:], bootstrap_value[None]], axis=0)
    pg_advantages = clipped_rhos * (rewards + discounts * vs_tp1 - values)
    return vs, pg_advantages


def impala_loss(forward, params, fragment: dict, gamma: float,
                value_coef: float, entropy_coef: float,
                rho_clip: float = 1.0, c_clip: float = 1.0,
                chunk: int = 1024):
    """The IMPALA loss of one fragment under ``params``.

    ``fragment``: obs [T, B, ...], bootstrap_obs [B, ...], actions [T, B],
    behaviour_logp, rewards [T, B], done [T, B] (terminated or truncated:
    the program cuts the bootstrap at both).
    """
    obs = fragment["obs"]
    T, B = obs.shape[:2]
    obs_all = jnp.concatenate([obs, fragment["bootstrap_obs"][None]], axis=0)
    logits, values = forward_in_chunks(
        forward, params, obs_all.reshape((T + 1) * B, *obs.shape[2:]), chunk
    )
    logits = logits.reshape(T + 1, B, -1)[:-1]
    values = values.reshape(T + 1, B)
    bootstrap_value, values = values[-1], values[:-1]
    logp_all = log_softmax(logits)
    target_logp = jnp.take_along_axis(
        logp_all, fragment["actions"][..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    discounts = gamma * (1.0 - fragment["done"].astype(F32))
    vs, pg_adv = vtrace_sequential(
        fragment["behaviour_logp"].astype(F32), target_logp,
        fragment["rewards"].astype(F32), discounts, values, bootstrap_value,
        rho_clip, c_clip,
    )
    pg_loss = -jnp.mean(target_logp * pg_adv)
    value_loss = 0.5 * jnp.mean(jnp.square(vs - values))
    entropy = jnp.mean(-jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1))
    return pg_loss + value_coef * value_loss - entropy_coef * entropy
