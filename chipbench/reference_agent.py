"""The plain reference of the AIMM agent: the dueling double DQN, its TD
step and its bookkeeping in NumPy float32, written from the documented
semantics and importing nothing of the program.

* Q-network: ReLU dense layers `w0, b0, w1, b1, ...`, then a dueling head
  Q(s, a) = V(s) + A(s, a) - mean_a A(s, a) (`w_v, b_v`, `w_a, b_a`).
* TD loss: y = r + gamma (1 - done) Q_target(s2, argmax_a Q(s2, a)), the
  loss sum_i (w_i (y_i - Q(s_i, a_i)))^2 / max(sum_i w_i, 1), with y held
  fixed; its gradient is written out by hand below.
* Adam (b1 0.9, b2 0.999, eps 1e-8, no weight decay) on the gradient
  scaled by min(1, clip / (global norm + 1e-9)); `train_steps` counts the
  steps taken, and the target network copies the online one whenever it
  reaches a multiple of `target_sync`.
* epsilon-greedy: eps = eps_end + (eps_start - eps_end) exp(-step / decay)
  on the agent's lifetime step count.
* replay: a ring of `capacity` transitions; a push writes at the pointer,
  which wraps, and the size saturates at the capacity."""
from __future__ import annotations

import numpy as np

f32 = np.float32
ADAM_B1, ADAM_B2, ADAM_EPS = f32(0.9), f32(0.999), f32(1e-8)


def _layers(params: dict) -> int:
    n = 0
    while f"w{n}" in params:
        n += 1
    return n


def forward(params: dict, s: np.ndarray, dtype=f32):
    """Q-values (B, A) of states `s` (B, D), and the activations the
    backward pass needs; `dtype` is the precision of every product and
    sum."""
    p = {k: np.asarray(v).astype(dtype) for k, v in params.items()}
    x = np.asarray(s).astype(dtype)
    acts = [x]
    for i in range(_layers(p)):
        x = np.maximum(x @ p[f"w{i}"] + p[f"b{i}"], dtype(0))
        acts.append(x)
    v = x @ p["w_v"] + p["b_v"]                          # (B, 1)
    a = x @ p["w_a"] + p["b_a"]                          # (B, A)
    q = v + a - a.mean(axis=-1, keepdims=True)
    return q.astype(f32), acts


def q_values(params: dict, s: np.ndarray, dtype=f32) -> np.ndarray:
    return forward(params, s, dtype)[0]


def td_loss_and_grads(params: dict, target: dict, batch: dict,
                      gamma: float, double: bool = True):
    """(loss, gradients by parameter name) of one minibatch."""
    s, a = np.asarray(batch["s"], f32), np.asarray(batch["a"])
    r, done = np.asarray(batch["r"], f32), np.asarray(batch["done"], f32)
    w = np.asarray(batch["w"], f32)
    q, acts = forward(params, s)
    rows = np.arange(len(a))
    q_sa = q[rows, a]
    q_next_t = q_values(target, batch["s2"])
    a_star = (np.argmax(q_values(params, batch["s2"]), axis=-1) if double
              else np.argmax(q_next_t, axis=-1))
    y = r + f32(gamma) * (f32(1) - done) * q_next_t[rows, a_star]
    err = (y - q_sa) * w
    norm = max(w.sum(dtype=f32), f32(1))
    loss = f32((err ** 2).sum(dtype=f32) / norm)

    g_q = np.zeros_like(q)                               # dL/dQ(s, .)
    g_q[rows, a] = -2 * err * w / norm
    n_act = q.shape[1]
    g_v = g_q.sum(axis=-1, keepdims=True)
    g_a = g_q - g_v / f32(n_act)
    h = acts[-1]
    grads = {"w_v": h.T @ g_v, "b_v": g_v.sum(0),
             "w_a": h.T @ g_a, "b_a": g_a.sum(0)}
    g_h = g_v @ np.asarray(params["w_v"], f32).T \
        + g_a @ np.asarray(params["w_a"], f32).T
    for i in reversed(range(_layers(params))):
        g_z = g_h * (acts[i + 1] > 0)
        grads[f"w{i}"] = acts[i].T @ g_z
        grads[f"b{i}"] = g_z.sum(0)
        g_h = g_z @ np.asarray(params[f"w{i}"], f32).T
    return loss, {k: v.astype(f32) for k, v in grads.items()}


def global_norm(grads: dict) -> f32:
    return f32(np.sqrt(sum((g.astype(f32) ** 2).sum(dtype=f32)
                           for g in grads.values())))


def td_step(params: dict, target: dict, m: dict, v: dict, train_steps: int,
            batch: dict, cfg: dict, ready: bool = True,
            moments: tuple | None = None) -> dict:
    """One TD step of the agent: returns the new `params`, `target`, Adam
    moments `m` / `v`, `train_steps`, and the step's `loss` and
    `grad_norm` (before the clip).  A step taken before the replay holds
    `min_replay` transitions (`ready` False) changes nothing but the
    moments' decay over a zero gradient.  `moments` (new m, new v), when
    given, stand in for the updated moments in Adam's step, so the step
    can be checked apart from the gradient."""
    loss, grads = td_loss_and_grads(params, target, batch, cfg["gamma"],
                                    cfg["double"])
    if not ready:
        grads = {k: np.zeros_like(g) for k, g in grads.items()}
    gnorm = global_norm(grads)
    scale = min(f32(1), f32(cfg["grad_clip"]) / (gnorm + f32(1e-9)))
    t = f32(train_steps + 1)
    bc1, bc2 = f32(1) - ADAM_B1 ** t, f32(1) - ADAM_B2 ** t
    lr = f32(cfg["lr"])
    new_p, new_m, new_v = {}, {}, {}
    for k, g in grads.items():
        g = g * scale
        new_m[k] = ADAM_B1 * np.asarray(m[k], f32) + (f32(1) - ADAM_B1) * g
        new_v[k] = (ADAM_B2 * np.asarray(v[k], f32)
                    + (f32(1) - ADAM_B2) * g * g)
        if moments is not None:
            new_m[k] = np.asarray(moments[0][k], f32)
            new_v[k] = np.asarray(moments[1][k], f32)
        step = (new_m[k] / bc1) / (np.sqrt(new_v[k] / bc2) + ADAM_EPS)
        new_p[k] = np.asarray(params[k], f32) - lr * step
    steps = train_steps + int(ready)
    sync = steps > 0 and steps % cfg["target_sync"] == 0
    new_t = {k: (new_p[k] if sync else np.asarray(target[k], f32))
             for k in params}
    return {"params": new_p, "target": new_t, "m": new_m, "v": new_v,
            "train_steps": steps, "loss": loss, "grad_norm": gnorm}


def epsilon(cfg: dict, step: int) -> f32:
    frac = np.exp(-f32(step) / f32(cfg["eps_decay"]), dtype=f32)
    return f32(cfg["eps_end"] + (cfg["eps_start"] - cfg["eps_end"]) * frac)


def replay_push(ring: dict, s, a, r, s2, done=0.0) -> dict:
    """The ring after one push (a new dict; the arrays are copied)."""
    out = {k: np.array(v) for k, v in ring.items()}
    i, cap = int(out["ptr"]), out["s"].shape[0]
    out["s"][i], out["a"][i], out["r"][i] = s, a, r
    out["s2"][i], out["done"][i] = s2, done
    out["ptr"] = np.int32((i + 1) % cap)
    out["size"] = np.int32(min(int(out["size"]) + 1, cap))
    return out
