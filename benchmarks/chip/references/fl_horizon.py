"""Plain reference of one FL horizon over a NOMA or OTA uplink, for any
payload (arXiv:2006.13044 Sec. II-IV; OTA: arXiv:2206.06679): everything
but the model's own equations, which a configuration's reference module
passes in as ``model``.

Written from the papers' equations and the repository's documented
conventions, importing nothing of the program: one round after another on
the host, one device after another (MAPEL solves the horizon's groups in
lockstep), every array in the precision the configuration states.

  channels   h = L(d) |h0|, d uniform in the disk, Rayleigh |h0| per round,
             drawn from PRNGKey(seed) with the program's documented folds
  schedule   lazy GWMIN: repeatedly take the (round, K-subset) vertex of
             largest weighted SIC sum rate at full power over the still
             free devices, subsets enumerated over the 24 strongest
             devices by weighted solo rate (ties to the lower id)
             update-aware: top-K of (estimated update norm x solo rate)
  power      MAPEL polyblock + coordinate polish, or full power
  budgets    c_k = R_k B t, b_k = floor(32 / max(I / c_k, 1))
  training   K clients, ``model.local_epoch`` of minibatch SGD each on its
             shard
  uplink     per-client DoReFa (max-abs scale) + FedAvg, or the OTA
             truncated-inversion superposition with receiver noise
  eval       ``model.accuracy`` on the whole test set after every round

A payload's ``model`` holds its equations:

  init(key, dtype)              the parameters, a dict of dicts of arrays
  local_epoch(params, xb, yb, lr)  one epoch of SGD over (nb, bs, ...)
                                minibatches, label -1 marking padding
  accuracy(params, x, y)        the test metric

``dtype`` is the precision of the model's parameters and arithmetic (and
of floating inputs); planning is float64 on the host in every case.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

LIGHT_SPEED = 299_792_458.0
OTA_SEED_OFFSET = 29
CANDIDATE_POOL = 24


# ---------------------------------------------------------------- physics

class Cell:
    def __init__(self, num_devices, cell):
        self.num_devices = num_devices
        for k, v in cell.items():
            setattr(self, k, v)
        self.wavelength_m = LIGHT_SPEED / self.carrier_hz
        n0 = 10.0 ** (self.noise_dbm_per_hz / 10.0) * 1e-3
        self.noise_power_w = n0 * self.bandwidth_hz
        self.dl_noise_w = n0 * self.downlink_bandwidth_hz


def positions(key, cell):
    k1, _ = jax.random.split(key)
    u = jax.random.uniform(k1, (cell.num_devices,))
    r = cell.cell_radius_m * jnp.sqrt(u)
    return jnp.maximum(r, cell.min_distance_m)


def large_scale(dist, cell):
    num = jnp.sqrt(cell.antenna_gain) * cell.wavelength_m
    den = 4.0 * jnp.pi * dist ** (cell.path_loss_exp / 2.0)
    return num / den


def round_gains(key, dist, cell, num_rounds):
    def one(k):
        ls = large_scale(dist, cell)
        kr, ki = jax.random.split(k)
        re = jax.random.normal(kr, dist.shape) * jnp.sqrt(0.5)
        im = jax.random.normal(ki, dist.shape) * jnp.sqrt(0.5)
        return ls * jnp.sqrt(re**2 + im**2)

    return jax.vmap(one)(jax.random.split(key, num_rounds))


def downlink_seconds(payload_bits, dist, cell):
    g = np.asarray(large_scale(dist, cell), np.float64)
    snr = cell.downlink_power_w * g * g / cell.dl_noise_w
    rate = cell.downlink_bandwidth_hz * np.log1p(snr) / np.log(2.0)
    return float(np.max(payload_bits / rate))


def sic_rates(powers, gains, noise):
    """log2(1 + SINR) per device, decoded strongest received power first
    (ties to the lower index); input order."""
    p = np.asarray(powers, np.float64)
    g = np.asarray(gains, np.float64)
    rx = p * g * g
    order = np.argsort(-rx, axis=-1, kind="stable")
    rx_s = np.take_along_axis(rx, order, axis=-1)
    suffix = np.cumsum(rx_s[..., ::-1], axis=-1)[..., ::-1]
    tail = np.concatenate([suffix[..., 1:], np.zeros_like(suffix[..., :1])],
                          axis=-1)
    r_s = np.log2(1.0 + rx_s / (tail + noise))
    out = np.empty_like(r_s)
    np.put_along_axis(out, order, r_s, axis=-1)
    return out


def solo_rates(gains, weights, pmax, noise):
    return weights * np.log2(1.0 + (pmax * gains**2) / noise)


# ---------------------------------------------------------------- MAPEL
# The polyblock outer approximation of Qian et al. (2009) for the weighted
# sum-rate MLFP of each scheduled group, run for the T groups of a horizon
# in lockstep: each group keeps its own vertex list, and the 80-step
# projection bisections, the feasibility back-substitutions and the
# coordinate-ascent polish are evaluated for all still-active groups at
# once.  Row i reproduces the one-group algorithm on group i.

def _objective(z, w):
    return np.exp(np.sum(w * np.log(np.maximum(z, 1e-300)), axis=-1))


def _min_powers(z, g, noise):
    """Least powers (decode order) reaching SINR targets z - 1, solved
    back to front; rows are groups."""
    p = np.zeros_like(z)
    g2 = g * g
    interference = np.full(z.shape[0], noise, dtype=np.float64)
    for i in range(z.shape[1] - 1, -1, -1):
        p[:, i] = (z[:, i] - 1.0) * interference / g2[:, i]
        interference = interference + p[:, i] * g2[:, i]
    return p


def _feasible(z, g, pmax, noise):
    ok = ~np.any(z < 1.0, axis=1)
    return ok & np.all(_min_powers(z, g, noise) <= pmax * (1.0 + 1e-12),
                       axis=1)


def _project(z, g, pmax, noise, tol=1e-12):
    """Largest lam in (0, 1] with 1 + lam (z - 1) feasible, per row."""
    lo, hi = np.zeros(z.shape[0]), np.ones(z.shape[0])
    active = np.ones(z.shape[0], dtype=bool)
    for _ in range(80):
        if not active.any():
            break
        mid = 0.5 * (lo + hi)
        feas = _feasible(1.0 + mid[:, None] * (z - 1.0), g, pmax, noise)
        lo = np.where(active & feas, mid, lo)
        hi = np.where(active & ~feas, mid, hi)
        active = active & ((hi - lo) >= tol)
    return 1.0 + lo[:, None] * (z - 1.0)


def _z_of_powers(p, g, noise):
    z = np.empty_like(p)
    for i in range(p.shape[1]):
        mu = np.sum(p[:, i:] * g[:, i:] ** 2, axis=1) + noise
        phi = np.sum(p[:, i + 1:] * g[:, i + 1:] ** 2, axis=1) + noise
        z[:, i] = mu / phi
    return z


def _group_rates(p, g, w, noise):
    return np.sum(w * sic_rates(p, g, noise), axis=-1)


def _polish(p0, g, w, pmax, noise, rounds=4, points=33):
    """Coordinate ascent over a 33-point power grid; a candidate replaces
    the incumbent only when it beats it by more than 1e-12."""
    p = np.array(p0, dtype=np.float64)
    grid = np.linspace(0.0, pmax, points)
    active = np.ones(p.shape[0], dtype=bool)
    for _ in range(rounds):
        improved = np.zeros(p.shape[0], dtype=bool)
        for k in range(p.shape[1]):
            best_v = _group_rates(p, g, w, noise)
            best_pk = p[:, k].copy()
            for cand in grid:
                trial = p.copy()
                trial[:, k] = cand
                v = _group_rates(trial, g, w, noise)
                upd = active & (v > best_v + 1e-12)
                best_v = np.where(upd, v, best_v)
                best_pk = np.where(upd, cand, best_pk)
                improved |= upd
            p[:, k] = np.where(active, best_pk, p[:, k])
        active &= improved
        if not active.any():
            break
    return p


def mapel(gains, weights, pmax, noise, eps=1e-3, max_iter=300):
    """Powers of G groups (rows, input order), decode order by gain."""
    gains = np.asarray(gains, np.float64)
    weights = np.asarray(weights, np.float64)
    n, k = gains.shape
    order = np.argsort(-gains, axis=1, kind="stable")
    g = np.take_along_axis(gains, order, axis=1)
    w = np.take_along_axis(weights, order, axis=1)
    if k == 1:
        return np.full((n, 1), pmax)
    verts = [[row] for row in 1.0 + pmax * g**2 / noise]
    best_z = _project(1.0 + pmax * g**2 / noise, g, pmax, noise)
    best_val = _objective(best_z, w)
    corner = _z_of_powers(np.full((n, k), pmax), g, noise)
    take = _objective(corner, w) > best_val
    best_z = np.where(take[:, None], corner, best_z)
    best_val = np.where(take, _objective(corner, w), best_val)
    it = np.zeros(n, dtype=int)
    done = np.zeros(n, dtype=bool)
    while True:
        active = [i for i in range(n)
                  if not done[i] and it[i] < max_iter and verts[i]]
        if not active:
            break
        popped = []
        for i in active:
            it[i] += 1
            vals = _objective(np.asarray(verts[i]), w[i])
            j = int(np.argmax(vals))
            v = verts[i].pop(j)
            if (float(vals[j]) - best_val[i]) / max(best_val[i], 1e-12) <= eps:
                done[i] = True
            else:
                popped.append((i, v))
        if not popped:
            continue
        rows = np.asarray([i for i, _ in popped])
        projs = _project(np.stack([v for _, v in popped]), g[rows], pmax,
                         noise)
        for (i, v), proj, val in zip(popped, projs,
                                     _objective(projs, w[rows])):
            if val > best_val[i]:
                best_val[i], best_z[i] = val, proj
            for j in range(k):
                if proj[j] < v[j] - 1e-12:
                    nv = v.copy()
                    nv[j] = proj[j]
                    verts[i].append(nv)
            if verts[i]:
                keep = (_objective(np.asarray(verts[i]), w[i])
                        > best_val[i] * (1 + eps / 4))
                verts[i] = [u for u, kp in zip(verts[i], keep) if kp]
    p_sorted = np.minimum(_min_powers(best_z, g, noise), pmax)
    cand_a = _polish(p_sorted, g, w, pmax, noise)
    cand_b = _polish(np.full((n, k), pmax), g, w, pmax, noise)
    use_b = _group_rates(cand_b, g, w, noise) > _group_rates(cand_a, g, w,
                                                               noise)
    powers = np.zeros((n, k))
    np.put_along_axis(powers, order,
                      np.where(use_b[:, None], cand_b, cand_a), axis=1)
    return powers


def horizon_powers(mode, gains_tk, weights_tk, pmax, noise):
    """(T, K) powers of the horizon's T groups."""
    if mode == "max":
        return np.full(np.shape(gains_tk), pmax, dtype=np.float64)
    if mode == "mapel":
        return mapel(gains_tk, weights_tk, pmax, noise)
    raise ValueError(f"the reference has no power mode {mode!r}")


# ---------------------------------------------------------------- schedule

def lazy_gwmin(gains_tm, weights, k, pmax, noise):
    """Rounds of K devices, each device at most once (constraint C1)."""
    num_rounds, num_devices = gains_tm.shape
    rounds = [()] * num_rounds
    avail, remaining = set(range(num_devices)), set(range(num_rounds))
    while remaining and avail:
        best = (-np.inf, None, None)
        for t in sorted(remaining):
            free = np.asarray(sorted(avail))
            if len(free) > CANDIDATE_POOL:
                solo = solo_rates(gains_tm[t, free], weights[free], pmax,
                                  noise)
                free = free[np.argsort(-solo, kind="stable")[:CANDIDATE_POOL]]
            kk = min(k, len(free))
            subs = np.array(list(itertools.combinations(sorted(free.tolist()),
                                                        kk)),
                            dtype=np.intp).reshape(-1, kk)
            g = gains_tm[t][subs]
            w = weights[subs]
            vals = np.sum(w * sic_rates(np.full(g.shape, pmax), g, noise),
                          axis=-1)
            i = int(np.argmax(vals))
            if vals[i] > best[0]:
                best = (float(vals[i]), tuple(subs[i].tolist()), t)
        _, subset, t = best
        rounds[t] = subset
        avail -= set(subset)
        remaining.discard(t)
    return rounds


def update_aware_scores(t, gains_tm, weights, pmax, noise, norms, seen):
    """Estimated update norm x weighted solo rate (Amiri et al.): devices
    never seen take the mean observed norm (1 before any), observed norms
    are floored at 1e-3 of that mean."""
    solo = solo_rates(gains_tm[t], weights, pmax, noise)
    est = norms.copy()
    default = max(float(est[seen].mean()) if seen.any() else 1.0, 1e-12)
    est[~seen] = default
    est[seen] = np.maximum(est[seen], 1e-3 * default)
    return est * solo


def regret(scores, devs, k):
    """How far the weakest of ``devs`` falls below the K-th best score,
    relative to it; 0 when ``devs`` are a top-K choice."""
    kth = float(np.sort(scores)[-k])
    worst = float(min(scores[d] for d in devs))
    return max(0.0, (kth - worst) / kth)


def dorefa(x, bits):
    """Eq. 7 with a per-tensor max-abs scale; 32 bits pass through."""
    if bits >= 32:
        return x
    a = jnp.float32(2.0) ** jnp.float32(bits) - 1.0
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    return jnp.round(a * jnp.clip(x / scale, -1.0, 1.0)) / a * scale


def adaptive_bits(payload_bits, budget):
    r = jnp.maximum(float(payload_bits)
                    / jnp.maximum(jnp.float32(budget), 1e-9), 1.0)
    return int(jnp.clip(jnp.floor(32.0 / r), 1.0, 32.0).astype(jnp.int32))


def ota_superpose(deltas, gains_k, agg_w, key, pmax, noise_std, threshold):
    """Truncated channel inversion: the PS receives the weighted sum of the
    participants' raw updates plus receiver noise scaled by
    1 / (sqrt(eta) sum w), eta the tightest participant's power cap."""
    leaves = [jax.tree_util.tree_leaves(d) for d in deltas]
    flat = jnp.stack([jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                                       for x in ls]) for ls in leaves])
    h = jnp.asarray(np.asarray(gains_k), jnp.float32)
    w = jnp.asarray(np.asarray(agg_w), jnp.float32)
    mask = (w > 0) & (h > 0) & (h >= jnp.float32(threshold) * jnp.max(h))
    energy = jnp.sum(flat * flat, axis=1)
    den = w * w * energy
    cap = jnp.where(mask & (den > 0), jnp.float32(pmax) * h * h
                    / jnp.maximum(den, 1e-30), jnp.inf)
    eta = jnp.min(cap)
    wsum = jnp.maximum(jnp.sum(jnp.where(mask, w, 0.0)), 1e-30)
    coeff = jnp.where(mask, w, 0.0) / wsum
    scale = jnp.where(jnp.isfinite(eta) & (eta > 0),
                      jnp.float32(noise_std) / (jnp.sqrt(eta) * wsum), 0.0)
    noise = scale * jax.random.normal(key, (flat.shape[1],), jnp.float32)
    out = sum(coeff[i] * flat[i] for i in range(flat.shape[0])) + noise
    tree = jax.tree_util.tree_structure(deltas[0])
    parts, start = [], 0
    for x in leaves[0]:
        parts.append(out[start:start + x.size].reshape(x.shape))
        start += x.size
    return jax.tree_util.tree_unflatten(tree, parts)


def inputs(x, dtype):
    """A model's input on the device: floating features in ``dtype``,
    token ids as they are."""
    x = jnp.asarray(x)
    return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x


def padded_shard(world, d, nb, bs, dtype):
    """Device d's shard as ``nb`` minibatches of ``bs`` rows, padded with
    zero rows labelled -1."""
    idx = world.shards[d]
    xs, ys = world.dataset.x_train, world.dataset.y_train
    x = np.zeros((nb * bs,) + xs.shape[1:], xs.dtype)
    y = np.full((nb * bs,) + ys.shape[1:], -1, ys.dtype)
    x[:len(idx)] = xs[idx]
    y[:len(idx)] = ys[idx]
    return (inputs(x.reshape((nb, bs) + xs.shape[1:]), dtype),
            jnp.asarray(y.reshape((nb, bs) + ys.shape[1:])))


def record_params(params):
    """The parameters as ``{"a.b": float32 array}``, the record's keys."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return {".".join(str(k.key) for k in path):
            np.asarray(v.astype(jnp.float32)) for path, v in flat}


def tree_norm(tree):
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                              for x in jax.tree_util.tree_leaves(tree))))


# ---------------------------------------------------------------- horizon

def run_instance(world, fl, cell_cfg, seed, *, model, dtype, follow):
    """One horizon from instance seed ``seed``; returns the record the
    benchmark compares (devices, bits, rates, times per round, accuracy
    per round, final parameters).

    ``follow`` (online policies only) is the device groups the horizon
    under test chose.  The reference then trains on those groups and
    records, as ``regret``, how far the worst of them fell below its own
    top-K score in any round: a choice between two near-equal scores is
    not a fault, and trajectories that part after one would hide every
    later round from the comparison.

    ``model`` is the payload's equations (see the module's docstring);
    ``dtype`` the precision of its parameters and arithmetic."""
    M, K, T = fl["num_devices"], fl["group_size"], fl["num_rounds"]
    bs, lr = fl["batch_size"], fl["learning_rate"]
    if T * K > M:
        raise ValueError("the reference schedules at most M devices")
    cell = Cell(M, cell_cfg)
    noise, pmax = cell.noise_power_w, cell.max_power_w
    key = jax.random.PRNGKey(seed)
    params = model.init(key, dtype)
    payload = sum(x.size for x in jax.tree_util.tree_leaves(params)) * 32
    sizes = np.asarray(world.sizes, np.float64)
    weights = sizes / sizes.sum()
    dist = positions(jax.random.fold_in(key, 1), cell)
    gains = np.asarray(round_gains(jax.random.fold_in(key, 2), dist, cell, T))
    dl_time = downlink_seconds(payload, dist, cell)
    ota = fl["uplink"] == "ota"
    base = jax.random.PRNGKey(seed + OTA_SEED_OFFSET)
    nb = int(-(-sizes.max() // bs))
    x_test = inputs(world.dataset.x_test, dtype)
    y_test = jnp.asarray(world.dataset.y_test)

    online = fl["scheduler"] == "update-aware"
    if online:
        norms, seen = np.zeros(M), np.zeros(M, bool)
    elif fl["scheduler"] == "lazy-gwmin":
        plan = lazy_gwmin(gains, weights, K, pmax, noise)
        idx_tk = np.asarray(plan, np.intp)
        plan_powers = horizon_powers(
            fl["power_mode"], gains[np.arange(T)[:, None], idx_tk],
            weights[idx_tk], pmax, noise)
    else:
        raise ValueError(f"the reference has no scheduler {fl['scheduler']!r}")

    rec = {"seed": seed, "devices": [], "bits": [], "rates": [],
           "times": [], "accs": [], "regret": 0.0}
    t_wall = 0.0
    for t in range(T):
        if online:
            scores = update_aware_scores(t, gains, weights, pmax, noise,
                                         norms, seen)
            devs = tuple(int(d) for d in
                         np.argsort(-scores, kind="stable")[:K])
            if follow is not None:
                devs = tuple(follow[t])
                rec["regret"] = max(rec["regret"], regret(scores, devs, K))
            idx = np.asarray(devs, np.intp)
            p = horizon_powers(fl["power_mode"], gains[t, idx][None],
                               weights[idx][None], pmax, noise)[0]
        else:
            devs = plan[t]
            idx = np.asarray(devs, np.intp)
            p = plan_powers[t]
        rates = sic_rates(p, gains[t, idx], noise)
        budgets = rates * cell.bandwidth_hz * cell.slot_seconds
        t_wall += (cell.slot_seconds if devs else 0.0) + dl_time
        agg_w = sizes[idx] / max(sizes[idx].sum(), 1.0)

        deltas, bits = [], []
        for j, d in enumerate(devs):
            xb, yb = padded_shard(world, d, nb, bs, dtype)
            new = params
            for _ in range(fl["local_epochs"]):
                new = model.local_epoch(new, xb, yb, jnp.asarray(lr, dtype))
            deltas.append(jax.tree_util.tree_map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                new, params))
            if online:
                norms[d] = tree_norm(deltas[-1])
                seen[d] = True
            b = (adaptive_bits(payload, budgets[j])
                 if fl["compression"] == "adaptive" else 32)
            bits.append(b)
        if ota:
            update = ota_superpose(
                deltas, gains[t, idx], agg_w,
                jax.random.fold_in(base, t), pmax, fl["ota_noise"],
                fl["ota_threshold"])
        else:
            update = jax.tree_util.tree_map(
                lambda *ds: sum(np.float32(w) * dorefa(x, b)
                                for w, b, x in zip(agg_w, bits, ds)),
                *deltas)
        params = jax.tree_util.tree_map(
            lambda p_, u: (p_.astype(jnp.float32) + u).astype(dtype),
            params, update)
        rec["devices"].append(tuple(devs))
        rec["bits"].append(np.asarray(bits, np.int64))
        rec["rates"].append(np.asarray(rates, np.float64))
        rec["times"].append(t_wall)
        rec["accs"].append(float(model.accuracy(params, x_test, y_test)))
    rec["times"] = np.asarray(rec["times"])
    rec["accs"] = np.asarray(rec["accs"])
    rec["params"] = record_params(params)
    return rec
