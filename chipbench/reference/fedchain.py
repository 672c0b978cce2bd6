"""Plain reference of one grid cell: chained FedAvg -> SGD with compressed
links, partial participation and, where the traffic asks, UCB selection.

It is written from the algorithms' descriptions, in straightforward
``jax.numpy``, one cell at a time, and imports nothing of the program under
test. What makes it comparable with the program cell for cell is that it
draws the same random numbers: a cell's randomness is a function of its
seed, and the derivation below is the one the engine documents.

It holds one client at a time. Every per-client computation (FedAvg's
local steps, SGD's client gradients, the selection values, the UCB probe,
the global loss) is a sequential map over the clients, and a round's uplink
is one scan over the clients in the round's order: each client's row is
made, compressed and added to the server's sum before the next client's.
SGD's K queries are a running sum in key order and the selection values a
sequential map, one query at a time; FedAvg's inner batch of queries stays
vectorised. So at a model's width the reference keeps one client's
parameters and one query's gradient and activations (``inner_batch`` of
them in a FedAvg step), besides the per-client feedback residuals that the
algorithm itself keeps.

The data comes from the model module: ``model.arrays(data, dtype)`` turns
the population's dict into (inputs, targets), each [N, n, ...]; integer
arrays such as token ids stay integers. A model whose configuration brings
weights the algorithm does not update (a frozen base under trained
adapters) defines ``frozen(config, key)``; the reference is given that
pytree, casts its floats to its own precision once and passes it to every
jitted step as an argument, behind the data, never as a constant; the
model's loss then takes it last: ``model.loss(params, X, y, l2, frozen)``.

Per cell (seed s, stepsize multiplier m, mask seed, selection seed, fold f):

* Keys. key = PRNGKey(s); four stage keys = split(key, 4). Stage 1 (FedAvg)
  takes its B1 = round(R/2) round keys from split(stage_key[0], B1), stage 2
  (SGD) its B2 = R - B1 - 1 from split(stage_key[2], B2). The Lemma H.2
  selection between them uses stage_key[1]. The scan runs R rows: B1 FedAvg
  rounds, one selection row, B2 SGD rounds.
* Participation. With a mask schedule: mk = fold_in(PRNGKey(mask_seed), f);
  row r draws u = uniform(split(mk, R)[r], (N,)) and keeps the S clients of
  smallest u. With UCB: the same keys from the selection seed drive the
  policy (below).
* A round key k yields k_sample, k_work = split(k); the comm stream is
  c = fold_in(k, 0x636D) and the downlink stream fold_in(c, 2).
* Client order. Every round enumerates all N clients in a Fisher-Yates
  order: for i < N, j_i = randint(split(k_sample, N)[i], i, N), swap i, j_i.
* QSGD with b bits on rows v [S, d] and key k: u = uniform(k, (S, d))
  (row i drawn alone, bitwise the same: ``uniform_row``), L = 2^b - 1,
  ||v|| per row, q = floor(|v|/||v|| L) + [u < frac], out =
  sign(v) ||v|| q / L. A parameter pytree compresses leaf by leaf in
  flattening order, leaf i with split(k, n_leaves)[i] (one leaf: k itself).
* Downlink (server error feedback): delta = x - ref + res, c = C(delta),
  clients hold ref + c, the server keeps res = delta - c, ref = ref + c.
* FedAvg round: clients start from the downlink reconstruction x_s; client
  i (key split(k_work, N)[i]) takes local steps, each the mean of
  ``inner_batch`` minibatch gradients (step keys split(key_i, steps), query
  keys split(step key, inner_batch)); it uplinks y_i - x_s. With uplink
  error feedback: d_i = y_i - x_s + e_i, c_i = C(d_i), x = x_s + sum over
  participants c_i / S (summed in the round's client order), participants
  keep e_i = d_i - c_i. Without: x = mean over participants of
  (x_s + c_i).
* SGD round: gradients at the downlink reconstruction, K queries a client
  (keys split(k_work, N K) in client-major order), their mean uplinked as
  g_i (+ e_i with feedback); x = x - eta * mean over participants of C(.).
  The output is the (1 - eta mu)^-r weighted average of the iterates, or
  the last.
* A minibatch query: idx = randint(key, (B,), 0, n); the loss of the
  client's rows idx; gradient by autodiff.
* Selection row: candidates x0 and the FedAvg iterate; k_sample, k_vals =
  split(stage_key[1]); every client (Fisher-Yates order of k_sample) scores
  both on the same K queries (keys split(k_vals, N K)); keep x0 if its mean
  value is not larger. SGD starts from the winner; feedback residuals are
  cleared there.
* UCB (per row, before the round, on the active stage's iterate x): probe
  every client once, v_i = value of a minibatch query with key
  split(fold_in(sel_key, 0x736C), N)[i]; reward of last round's participants
  = last probe - v; running mean over their counts; score = mean +
  c sqrt(log(t + 1) / max(count, 1)), never-chosen clients first; the S
  highest scores participate (ties to the lower index).
* History: the global loss of the active stage's output after each row:
  the mean over clients of the loss over the client's first
  ``eval_per_client`` samples (the configuration's key; all n where it
  names none), taken in blocks of B samples, a last short block weighted
  by its size; the selection row records the winner's. Bits follow the
  closed forms: uplink S_r (32 + d (b + 1)) per QSGD leaf (32 d
  uncompressed), the same for the downlink, 2 * 32 N up and 2 * 32 D N
  down on the selection row, and 32 N up for each UCB probe.

``dtype`` sets the precision of every float array and ``precision`` that
of the matrix products: float32 at ``highest`` for the reference; the
control drops one step (``high``, three bf16 passes), and bfloat16 arrays
are a further reading.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

COMM_TAG = 0x636D
DOWN_TAG = 2
PROBE_TAG = 0x736C


def leaves(tree):
    return jax.tree.leaves(tree)


def _tree_map(f, *trees):
    return jax.tree.map(f, *trees)


def uniform_row(key, shape, row):
    """Row ``row`` of ``jax.random.uniform(key, shape)`` (float32, ``shape``
    = (rows, width)), drawn without the other rows.

    JAX's partitionable threefry (``jax_threefry_partitionable``, on by
    default) draws element (i, j) from the 64-bit counter i * width + j,
    split into a high and a low 32-bit word; its 32 bits are the two
    outputs' xor, and the float is 1.0's exponent over the top 23 bits,
    minus 1.0."""
    rows, width = shape
    if not jax.config.jax_threefry_partitionable:
        raise NotImplementedError("uniform_row follows the partitionable "
                                  "threefry")
    if rows * width >= 2 ** 32:
        raise NotImplementedError("counters of 2**32 and more need a high "
                                  "word")
    lo = (jnp.asarray(row).astype(jnp.uint32) * jnp.uint32(width)
          + jnp.arange(width, dtype=jnp.uint32))
    b1, b2 = threefry2x32_p.bind(key[0], key[1], jnp.zeros_like(lo), lo)
    bits = jax.lax.shift_right_logical(b1 ^ b2, jnp.uint32(32 - 23))
    bits = bits | jnp.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32) - jnp.float32(1.0)


@dataclasses.dataclass
class CellResult:
    history: np.ndarray  # [R]
    x_hat: object
    bits_up: np.ndarray  # [R]
    bits_down: np.ndarray  # [R]
    kept: bool
    masks: np.ndarray  # [R, N]
    margins: np.ndarray  # [R] UCB: relative gap of the S-th score to the next


class Reference:
    """Runs cells of one configuration under one traffic mix."""

    def __init__(self, model, config: dict, traffic: dict, data: dict, *,
                 frozen=None, dtype=jnp.float32, precision: str = "highest",
                 fault: str | None = None):
        self.model = model
        self.cfg = config
        self.traffic = traffic
        self.dtype = jnp.dtype(dtype)
        self.precision = precision
        self.fault = fault
        self.set_population(data, frozen)
        self.batch = model.batch(config)
        self.l2 = float(config["l2"])
        m = config["method"]
        self.local, self.glob = m["local"], m["global"]
        self.sel_k = int(m["selection_k"])
        self.rounds = int(traffic["rounds"])
        self.b1 = max(1, int(round(m["local_fraction"] * self.rounds)))
        self.b2 = max(1, self.rounds - self.b1 - 1)
        self.s_part = max(1, int(round(traffic["participation"]
                                       * self.n_clients)))
        self.up, self.down = traffic["uplink"], traffic["downlink"]
        self.policy = traffic.get("policy")
        self._jit()

    def set_population(self, data: dict, frozen=None):
        """The data every jitted step takes as an argument (never as a
        constant, so a new population of the same shape compiles nothing):
        the model's (inputs, targets), each [N, n, ...], and behind them
        the frozen weights where the configuration brings them, their
        floats cast to the reference's precision."""
        self.data = tuple(self.model.arrays(data, self.dtype))
        self.n_clients, self.n_per = self.data[0].shape[:2]
        if frozen is not None:
            self.data += (jax.tree.map(
                lambda l: l.astype(self.dtype)
                if jnp.issubdtype(l.dtype, jnp.floating) else l, frozen),)
        self.n_eval = int(self.cfg.get("eval_per_client", self.n_per))
        if not 0 < self.n_eval <= self.n_per:
            raise ValueError(f"eval_per_client {self.n_eval} is not in 1.."
                             f"{self.n_per}, the samples a client holds")

    # -- building blocks -------------------------------------------------

    def _loss(self, data, x, X, y):
        return self.model.loss(x, X, y, self.l2, *data[2:])

    def _query(self, data, cid, key):
        idx = jax.random.randint(key, (self.batch,), 0, self.n_per)
        return data[0][cid][idx], data[1][cid][idx]

    def _grad(self, data, x, cid, key):
        X, y = self._query(data, cid, key)
        return jax.grad(self._loss, argnums=1)(data, x, X, y)

    def _mean_grad(self, data, x, cid, keys):
        """Mean of the minibatch gradients of ``keys``, one query at a
        time: a running sum in key order, over their number."""
        def add(acc, key):
            return _tree_map(jnp.add, acc, self._grad(data, x, cid, key)), None

        total, _ = jax.lax.scan(add, self._grad(data, x, cid, keys[0]),
                                keys[1:])
        return _tree_map(lambda t: t / keys.shape[0], total)

    def _value(self, data, x, cid, key):
        X, y = self._query(data, cid, key)
        return self._loss(data, x, X, y)

    def _global_loss(self, data, x):
        """Mean over clients of the loss over each client's first
        ``n_eval`` samples, in blocks of ``batch``: one block's
        activations at a time."""
        full, rest = divmod(self.n_eval, self.batch)

        def client(shard):
            blocks = _tree_map(lambda a: a[:full * self.batch].reshape(
                (full, self.batch) + a.shape[1:]), shard)
            total = self.batch * jnp.sum(jax.lax.map(
                lambda blk: self._loss(data, x, *blk), blocks))
            if rest:
                tail = _tree_map(lambda a: a[full * self.batch:self.n_eval],
                                 shard)
                total = total + rest * self._loss(data, x, *tail)
            return total / self.n_eval

        return jnp.mean(jax.lax.map(client, data[:2]))

    def _order(self, key):
        """Fisher-Yates over all N clients."""
        n = self.n_clients
        keys = jax.random.split(key, n)
        js = jax.vmap(lambda k, i: jax.random.randint(k, (), i, n))(
            keys, jnp.arange(n, dtype=jnp.int32))

        def swap(i, idx):
            a, b = idx[i], idx[js[i]]
            return idx.at[i].set(b).at[js[i]].set(a)

        return jax.lax.fori_loop(0, n, swap, jnp.arange(n, dtype=jnp.int32))

    def _qsgd_rows(self, v, u, bits):
        u = u.astype(v.dtype)
        norm = jnp.sqrt(jnp.sum(v * v, axis=1, keepdims=True))
        norm = jnp.maximum(norm, jnp.asarray(1e-30, v.dtype))
        levels = jnp.asarray(2.0 ** bits - 1.0, v.dtype)
        scaled = jnp.abs(v) / norm * levels
        lo = jnp.floor(scaled)
        q = lo + (u < scaled - lo).astype(v.dtype)
        return jnp.sign(v) * norm * (q / levels)

    def _compress_row(self, tree, key, leg, rows, row):
        """Leaf-wise compression of row ``row`` of a [rows, ...] payload,
        given that row alone: the same numbers as compressing all rows."""
        if leg["compressor"] == "identity":
            return tree
        if leg["compressor"] != "qsgd":
            raise ValueError(f"reference knows identity and qsgd, not "
                             f"{leg['compressor']!r}")
        flat, treedef = jax.tree.flatten(tree)
        keys = [key] if len(flat) == 1 else list(
            jax.random.split(key, len(flat)))
        out = []
        for l, k in zip(flat, keys):
            u = uniform_row(k, (rows, l.size), row)
            out.append(self._qsgd_rows(l.reshape(1, -1), u[None],
                                       leg["qsgd_bits"]).reshape(l.shape))
        return jax.tree.unflatten(treedef, out)

    def _downlink(self, x, ref, res, key):
        if self.down["compressor"] == "identity":
            return x, x, _tree_map(jnp.zeros_like, x)
        delta = _tree_map(lambda a, r, e: a - r + e, x, ref, res)
        c = self._compress_row(delta, key, self.down, 1, 0)
        recon = _tree_map(jnp.add, ref, c)
        return recon, recon, _tree_map(jnp.subtract, delta, c)

    def _uplink(self, x_base, payload, order, mask, ures, key, eta):
        """x_base - eta * mean over participants of the compressed payloads,
        one client at a time in the round's order; with uplink error
        feedback each payload carries its client's residual, and
        participants keep the new one. ``payload(cid, pos)`` makes the
        payload of client ``cid``, at position ``pos`` of the order.
        Returns (x, residuals)."""
        n = self.n_clients
        m = mask[order]
        w = m.astype(self.dtype)
        if self.fault == "half_clients":
            # planted fault: only the first half of the rows enter the mean
            w = w * (jnp.arange(n) < n // 2).astype(self.dtype)
        total = jnp.maximum(jnp.sum(w), 1.0)
        ef = self.up.get("error_feedback", False)

        def client(carry, pos):
            acc, ures = carry
            cid = order[pos]
            p = payload(cid, pos)
            if ef:
                e = _tree_map(lambda t: t[cid], ures)
                p = _tree_map(jnp.add, p, e)
            c = self._compress_row(p, key, self.up, n, pos)
            acc = _tree_map(lambda a, cc: a + w[pos] * cc, acc, c)
            if ef:
                keep = _tree_map(lambda d, cc, ee: jnp.where(
                    m[pos] > 0, d - cc, ee), p, c, e)
                ures = _tree_map(lambda t, v: t.at[cid].set(v), ures, keep)
            return (acc, ures), None

        acc = _tree_map(jnp.zeros_like, x_base)
        (acc, ures), _ = jax.lax.scan(client, (acc, ures),
                                      jnp.arange(n, dtype=jnp.int32))
        x = _tree_map(lambda xb, a: xb - eta * a / total, x_base, acc)
        return x, ures

    # -- rounds ----------------------------------------------------------

    def _fedavg_round(self, data, x, ref, dres, ures, key, mask, eta):
        k_sample, k_local = jax.random.split(key)
        order = self._order(k_sample)
        ckey = jax.random.fold_in(key, COMM_TAG)
        x_s, ref, dres = self._downlink(x, ref, dres,
                                        jax.random.fold_in(ckey, DOWN_TAG))
        steps, inner = self.local["local_steps"], self.local["inner_batch"]
        keys = jax.random.split(k_local, self.n_clients)

        def local(cid, pos):
            def step(y, ks):
                # the inner batch stays vectorised: computed in turn, a lone
                # query's matrix-vector product fuses with the regulariser's
                # add on XLA:CPU, and the logreg replay leaves the recorded
                # vectorised numbers by more than rounding allows for
                qs = jax.random.split(ks, inner)
                gs = jax.vmap(lambda q: self._grad(data, y, cid, q))(qs)
                g = _tree_map(lambda a: jnp.mean(a, axis=0), gs)
                return _tree_map(lambda a, b: a - eta * b, y, g), None

            y, _ = jax.lax.scan(step, x_s, jax.random.split(keys[pos], steps))
            return _tree_map(lambda a, b: a - b, y, x_s)

        x_new, ures = self._uplink(x_s, local, order, mask, ures, ckey, -1.0)
        if self.fault == "frozen":
            x_new = x
        return x_new, ref, dres, ures

    def _sgd_round(self, data, x, avg, wprime, ref, dres, ures, key, mask,
                   eta):
        k_sample, k_grad = jax.random.split(key)
        order = self._order(k_sample)
        ckey = jax.random.fold_in(key, COMM_TAG)
        x_b, ref, dres = self._downlink(x, ref, dres,
                                        jax.random.fold_in(ckey, DOWN_TAG))
        k = self.glob["k"]
        qkeys = jax.random.split(k_grad, self.n_clients * k).reshape(
            self.n_clients, k, -1)

        def client(cid, pos):
            return self._mean_grad(data, x_b, cid, qkeys[pos])

        x_new, ures = self._uplink(x, client, order, mask, ures, ckey, eta)
        if self.fault == "frozen":
            x_new = x
        decay = jnp.clip(1.0 - eta * self.glob["mu_avg"], 0.0, 1.0)
        wprime = 1.0 + decay * wprime
        avg = _tree_map(lambda a, b: a + (b - a) / wprime, avg, x_new)
        return x_new, avg, wprime, ref, dres, ures

    def _select(self, data, anchor, cand, key):
        k_sample, k_vals = jax.random.split(key)
        order = self._order(k_sample)
        n, k = self.n_clients, self.sel_k
        qkeys = jax.random.split(k_vals, n * k).reshape(n, k, -1)

        def values(a):  # one client's mean value of each candidate
            cid, ks = a
            per = jax.lax.map(lambda q: [self._value(data, x, cid, q)
                                         for x in (anchor, cand)], ks)
            return [jnp.mean(v) for v in per]

        per = jax.lax.map(values, (order, qkeys))
        keep = jnp.mean(per[0]) <= jnp.mean(per[1])
        best = _tree_map(lambda a, b: jnp.where(keep, a, b), anchor, cand)
        return best, keep

    def _ucb(self, data, x, st, key):
        counts, values, last_probe, last_mask, t = st
        n = self.n_clients
        pkeys = jax.random.split(jax.random.fold_in(key, PROBE_TAG), n)
        v = jax.lax.map(lambda a: self._value(data, x, *a),
                        (jnp.arange(n, dtype=jnp.int32), pkeys)
                        ).astype(jnp.float32)
        reward = last_probe - v
        cnt = jnp.maximum(counts, 1.0)
        values = jnp.where(last_mask > 0,
                           values + (reward - values) / cnt, values)
        t = t + 1.0
        bonus = self.policy.get("ucb_c", 1.0) * jnp.sqrt(jnp.log(t + 1.0)
                                                         / cnt)
        score = jnp.where(counts < 0.5, jnp.inf, values + bonus)
        ranks = jnp.argsort(jnp.argsort(-score, stable=True), stable=True)
        mask = (ranks < self.s_part).astype(jnp.float32)
        # how far the S-th score lies above the next: a choice that rounding
        # can flip has a margin near zero (ties among the untried: +inf)
        top = -jnp.sort(-score)
        edge, nxt = top[self.s_part - 1], top[self.s_part]
        margin = jnp.where(jnp.isinf(edge), jnp.inf,
                           (edge - nxt) / jnp.maximum(jnp.abs(edge), 1e-30))
        return mask, (counts + mask, values, v, mask, t), margin

    def _jit(self):
        self.j_fedavg = jax.jit(self._fedavg_round)
        self.j_sgd = jax.jit(self._sgd_round)
        self.j_select = jax.jit(self._select)
        self.j_loss = jax.jit(self._global_loss)
        self.j_ucb = jax.jit(self._ucb)

    # -- bits ------------------------------------------------------------

    def _leg_bits(self, leg, dims):
        """Per-client bits of one pytree on ``leg``, in float32 as billed."""
        total = 0
        for d in dims:
            if leg["compressor"] == "identity":
                total = total + np.float32(32.0 * d)
            else:
                total = total + (np.float32(32.0) + np.float32(d)
                                 * (np.float32(leg["qsgd_bits"])
                                    + np.float32(1.0)))
        return np.float32(total)

    # -- one cell --------------------------------------------------------

    def masks(self, mask_seed: int, fold: int):
        n_rows = self.b1 + 1 + self.b2
        if self.traffic["participation"] >= 1.0:
            return np.ones((n_rows, self.n_clients), np.float32)
        mk = jax.random.fold_in(jax.random.PRNGKey(mask_seed), fold)
        u = np.asarray(jax.vmap(
            lambda k: jax.random.uniform(k, (self.n_clients,)))(
                jax.random.split(mk, n_rows)))
        ranks = np.argsort(np.argsort(u, axis=1, kind="stable"), axis=1,
                           kind="stable")
        return (ranks < self.s_part).astype(np.float32)

    def run_cell(self, x0, *, seed: int, mult: float, mask_seed: int,
                 sel_seed: int, fold: int, rows: int | None = None
                 ) -> CellResult:
        """Replay one cell; ``rows`` stops after that many schedule rows."""
        with jax.default_matmul_precision(self.precision):
            return self._run_cell(x0, seed, mult, mask_seed, sel_seed, fold,
                                  rows)

    def _run_cell(self, x0, seed, mult, mask_seed, sel_seed, fold, rows):
        n_rows = self.b1 + 1 + self.b2
        rows = n_rows if rows is None else rows
        x0 = _tree_map(lambda l: l.astype(self.dtype), x0)
        dims = [int(np.prod(l.shape)) for l in leaves(x0)]
        key = jax.random.PRNGKey(seed)
        stage = jax.random.split(key, 4)
        rk1 = jax.random.split(stage[0], self.b1)
        rk2 = jax.random.split(stage[2], self.b2)
        # stepsizes as the engine forms them: float32 base times multiplier
        eta1 = jnp.asarray(np.float32(self.local["eta"]) * np.float32(mult),
                           self.dtype)
        eta2 = jnp.asarray(np.float32(self.glob["eta"]) * np.float32(mult),
                           self.dtype)
        ef = self.up.get("error_feedback", False)
        ures = (_tree_map(lambda l: jnp.zeros((self.n_clients,) + l.shape,
                                              self.dtype), x0)
                if ef else None)
        ref = _tree_map(jnp.zeros_like, x0)
        dres = _tree_map(jnp.zeros_like, x0)
        sched = None if self.policy else self.masks(mask_seed, fold)
        if self.policy:
            sel_keys = jax.random.split(
                jax.random.fold_in(jax.random.PRNGKey(sel_seed), fold),
                n_rows)
            z = jnp.zeros((self.n_clients,), jnp.float32)
            pst = (z, z, z, z, jnp.zeros((), jnp.float32))
        up_bits = self._leg_bits(self.up, dims)
        down_bits = self._leg_bits(self.down, dims)
        probe_bits = np.float32(32.0 * self.n_clients) if self.policy else \
            np.float32(0.0)
        x = x0
        anchor = x0
        avg = wprime = None
        kept = False
        hist, bu, bd, masks, margins = [], [], [], [], []
        for r in range(rows):
            in_stage1 = r < self.b1
            sel_row = r == self.b1
            if r == self.b1 + 1:  # hand-off into SGD from the anchor
                x = anchor
                avg, wprime = anchor, jnp.asarray(1.0, self.dtype)
                if ef:
                    ures = _tree_map(jnp.zeros_like, ures)
                dres = _tree_map(jnp.zeros_like, dres)
            if self.policy:
                mask, pst, margin = self.j_ucb(self.data, x, pst, sel_keys[r])
                mask = np.asarray(mask)
                margins.append(float(margin))
            else:
                mask = sched[r]
            masks.append(mask)
            s_r = np.float32(np.sum(mask, dtype=np.float32))
            if sel_row:
                anchor, keep = self.j_select(self.data, x0, x, stage[1])
                kept = bool(keep)
                hist.append(float(self.j_loss(self.data, anchor)))
                bu.append(np.float32(2.0 * 32.0 * self.n_clients)
                          + probe_bits)
                bd.append(np.float32(2.0 * 32.0 * sum(dims)
                                     * self.n_clients))
                continue
            if in_stage1:
                x, ref, dres, ures = self.j_fedavg(
                    self.data, x, ref, dres, ures, rk1[r], jnp.asarray(mask), eta1)
                out = x
            else:
                x, avg, wprime, ref, dres, ures = self.j_sgd(
                    self.data, x, avg, wprime, ref, dres, ures, rk2[r - self.b1 - 1],
                    jnp.asarray(mask), eta2)
                out = x if self.glob["output_mode"] == "last" else avg
            hist.append(float(self.j_loss(self.data, out)))
            bu.append(np.float32(s_r * up_bits) + probe_bits)
            bd.append(np.float32(s_r * down_bits))
        if rows > self.b1 + 1:
            x_hat = x if self.glob["output_mode"] == "last" else avg
        else:
            x_hat = x
        return CellResult(
            history=np.asarray(hist, np.float64),
            x_hat=_tree_map(lambda l: np.asarray(l.astype(jnp.float32)),
                            x_hat),
            bits_up=np.asarray(bu, np.float32),
            bits_down=np.asarray(bd, np.float32),
            kept=kept, masks=np.asarray(masks, np.float32),
            margins=np.asarray(margins, np.float64))

