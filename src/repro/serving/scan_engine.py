"""Vectorized simulation engine: the whole control plane as one
jit-compiled `lax.scan` over array-resident per-device state
(DESIGN.md §13).

The python engine replays the control plane per request — estimator
banks as dicts of objects, detectors as scalar accumulators, a python
loop over the trace. That is faithful but O(N) python-interpreter work;
at a million devices x ten million requests it is hours. This module
re-expresses the *same* math as a fixed-size array program:

**Column layout.** Requests are packed into an ``(L, D)`` matrix — one
column per device, row ``k`` holding each device's k-th request
(``L = max requests per device``; absent cells masked by ``valid``).
One `lax.scan` walks the L rows carrying ``(D,)`` state vectors
(estimator state, change-point statistics, controller mode / cooldown /
reference level) updated **elementwise** under the row's valid mask.
No per-device gather/scatter ever happens — XLA:CPU does not alias
scan-carry buffers for scatters, so the obvious one-step-per-request
formulation degrades to O(N*D); the column program is O(L*D) = O(N)
with pure vector ops. Per-device state evolution is independent across
devices, so row-major processing is equivalent to arrival order; event
records carry the original request index and are re-sorted afterwards.

**Exactness.** Every update mirrors the python classes op-for-op in
float64 (EWMA recurrence, numpy-interpolation percentile over a ring
buffer, CUSUM / Page-Hinkley with the shared self-normalizing scale,
the controller's cooldown/re-anchor walk), so selections, modes, and
switch events reproduce the python engine exactly; budget estimates
agree to the ULP-level tolerance the estimator-series tests already
grant the blocked closed forms. Selection, hedging masks, fallback
draws, and the RNG consumption order are *shared* with the python
engine (`ControlPlane.finish_static` / `finish_adaptive`), not
re-implemented.

**Sharding.** All ops are elementwise across the device axis, so the
fleet shards trivially: `shards=S` pads D to a multiple of S and wraps
the program in `jax.shard_map` over an S-device mesh — bitwise
identical to the unsharded run. CPU CI gets its mesh from
`repro.utils.config.configure(host_devices=N)`.
"""

from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from repro.serving.control import (CusumDetector, PageHinkleyDetector)
from repro.serving.fleet import EstimatorBank
from repro.serving.network import (EWMAEstimator, MeanEstimator,
                                   ObservedEstimator, PercentileEstimator)

_DEFAULT_PARAM = {"ewma": 0.2, "pctl": 90.0}


class BankDesc(NamedTuple):
    """Static description of one estimator bank — everything the array
    program needs, hashable for the compile cache."""

    kind: str                # observed | mean | ewma | pctl
    param: float             # ewma alpha / pctl q (0.0 otherwise)
    window: int              # pctl ring size (0 otherwise)
    lag: int
    prior_override: Optional[float] = None   # instance-level prior


class CtrlDesc(NamedTuple):
    """Static description of an `AdaptiveController` for the array
    program: monitor bank, detector parameters, mode-walk constants."""

    monitor: BankDesc
    det_kind: str            # cusum | ph
    threshold: float
    drift: float             # cusum drift / ph delta
    fixed_scale: Optional[float]
    scale_beta: float
    min_scale: float
    n_modes: int
    start: int
    cooldown: int
    scale_frac: float
    table: tuple             # per-mode-spec BankDescs (None = identity)


# --------------------------------------------------------------------------
# Descriptor extraction (python objects -> static descs)
# --------------------------------------------------------------------------

def _desc_from_spec(spec: str, lag: int) -> BankDesc:
    head, _, arg = spec.partition(":")
    param = float(arg) if arg else _DEFAULT_PARAM.get(head, 0.0)
    window = 64 if head == "pctl" else 0
    return BankDesc(head, param, window, int(lag))


def _desc_from_instance(est, lag: int) -> BankDesc:
    """Translate a prebuilt estimator instance. Only cold instances
    translate — a warm one carries python-side state the array program
    does not ingest."""
    if type(est) is ObservedEstimator:
        kind, param, window, cold = "observed", 0.0, 0, True
    elif type(est) is MeanEstimator:
        kind, param, window, cold = "mean", 0.0, 0, True
    elif type(est) is EWMAEstimator:
        kind, param, window = "ewma", est.alpha, 0
        cold = est._est is None
    elif type(est) is PercentileEstimator:
        kind, param, window = "pctl", est.q, est.window
        cold = not est._buf
    else:
        raise ValueError(
            f"engine='scan' cannot translate a custom estimator "
            f"({type(est).__name__}); use a registry spec string or "
            f"engine='python'")
    if not cold:
        raise ValueError(
            f"engine='scan' needs a cold estimator instance; this "
            f"{kind} estimator already holds observations")
    prior = None if est.prior is None else float(est.prior)
    return BankDesc(kind, param, window, int(lag), prior_override=prior)


def _static_desc(plane) -> Optional[BankDesc]:
    """The static path's budget estimator as a BankDesc (None =
    identity: budget from the observed upload time)."""
    est = plane.router.t_estimator
    if est is None:
        return None
    if isinstance(est, EstimatorBank):
        if isinstance(est.spec, str):
            return _desc_from_spec(est.spec, est.lag)
        return _desc_from_instance(est.spec, est.lag)
    return _desc_from_instance(est, 0)


def ctrl_desc_from_controller(ctrl, *, lag: int = 0,
                              table_specs=None) -> CtrlDesc:
    """Translate an `AdaptiveController` into the column program's
    `CtrlDesc`. Shared with the cluster engine
    (serving/cluster_engine.py), which runs the same controller kernel
    without a `ControlPlane` around it: the cluster only consumes the
    mode / switch-event outputs, so it passes ``table_specs=(None,)``
    to keep the per-mode estimator lanes trivial."""
    det = ctrl._detector_template
    if type(det) is CusumDetector:
        kind, drift = "cusum", det.drift
    elif type(det) is PageHinkleyDetector:
        kind, drift = "ph", det.delta
    else:
        raise ValueError(
            f"engine='scan' cannot translate a custom detector "
            f"({type(det).__name__}); use 'cusum'/'ph' or "
            f"engine='python'")
    if det.statistic != 0.0:
        raise ValueError("engine='scan' needs a pristine detector "
                         "template (statistic != 0)")
    specs = (tuple(table_specs) if table_specs is not None else
             tuple(dict.fromkeys(m.t_estimator for m in ctrl.modes)))
    table = tuple(
        None if spec is None else _desc_from_spec(spec, lag)
        for spec in specs)
    return CtrlDesc(
        monitor=_desc_from_spec(ctrl.monitor, 0), det_kind=kind,
        threshold=det.threshold, drift=drift,
        fixed_scale=det.fixed_scale, scale_beta=det.scale_beta,
        min_scale=det.min_scale, n_modes=len(ctrl.modes),
        start=ctrl.start, cooldown=ctrl.cooldown,
        scale_frac=ctrl.scale_frac, table=table)


def _ctrl_desc(plane) -> CtrlDesc:
    return ctrl_desc_from_controller(plane.controller, lag=plane.lag)


# --------------------------------------------------------------------------
# Column packing: (N,) request stream -> (L, D) per-device columns
# --------------------------------------------------------------------------

class _Packed(NamedTuple):
    t_mat: np.ndarray        # (L, D) f64, 0 in absent cells
    valid: np.ndarray        # (L, D) bool
    order: np.ndarray        # (N,) request indices in (device, k) order
    k_s: np.ndarray          # (N,) row of request order[j]
    dev_s: np.ndarray        # (N,) column of request order[j]
    r_idx: np.ndarray        # (L, D) original request index (-1 absent)


def _pack_columns(t: np.ndarray, dev: np.ndarray, D: int) -> _Packed:
    n = len(t)
    counts = np.bincount(dev, minlength=D)
    L = int(counts.max()) if n else 0
    order = np.argsort(dev, kind="stable")    # device-major, arrival-
    dev_s = dev[order]                        # ordered within device
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    k_s = np.arange(n) - starts[dev_s]
    t_mat = np.zeros((L, D))
    valid = np.zeros((L, D), bool)
    r_idx = np.full((L, D), -1, np.int64)
    t_mat[k_s, dev_s] = t[order]
    valid[k_s, dev_s] = True
    r_idx[k_s, dev_s] = order
    return _Packed(t_mat, valid, order, k_s, dev_s, r_idx)


def _unpack(p: _Packed, mat, dtype=np.float64) -> np.ndarray:
    out = np.empty(len(p.order), dtype)
    out[p.order] = np.asarray(mat)[p.k_s, p.dev_s]
    return out


# --------------------------------------------------------------------------
# The array program (built lazily so jax imports stay off the cold path)
# --------------------------------------------------------------------------

def _topm_size(q: float, n_rows: int, cap: int = 8):
    """How deep below the maximum a q-th percentile read can reach when
    at most `n_rows` values are ever seen: ranks lo/hi stay within the
    top `(n_rows-1) - floor(q/100*(n_rows-1)) + 1` order statistics.
    Returns that depth when it is small enough to keep as explicit
    (D,)-vector state, else None."""
    if q < 50.0:
        return None
    m = (n_rows - 1) - math.floor((q / 100.0) * (n_rows - 1)) + 1
    return m if m <= cap else None


def _unfused(prod, jnp):
    """Round a product separately before it feeds an add, so the XLA
    CPU backend cannot contract ``a*b + c`` into one fused
    multiply-add — python/numpy round the product and the sum
    separately, and engine parity here is bitwise.

    The guard is ``where(prod == prod, prod, 0.0)``: NaN semantics
    keep the compiler from proving the predicate true, and a select
    (unlike optimization_barrier or a bitcast round-trip, both erased
    before LLVM's contraction pass) survives to codegen. Two guarded
    products may feed one add — the select-merge rule only fuses
    selects sharing a predicate, and each guard's predicate is its own
    product. Only ever wrap products that are finite on lanes whose
    value is used (a used-NaN lane would turn into 0.0)."""
    return jnp.where(prod == prod, prod, 0.0)


def _core_init(desc: BankDesc, D: int, jnp, n_rows=None):
    if desc.kind == "ewma":
        return {"est": jnp.zeros(D), "seen": jnp.zeros(D, bool)}
    if desc.kind == "pctl":
        # Three layouts, specialized at trace time (n_rows = scan
        # length, static):
        #  - `top`: at most `n_rows` <= window values ever arrive AND
        #    the percentile only reads the top few order statistics —
        #    keep just those, maintained by an O(m) min/max chain of
        #    (D,) ops.
        #  - `sbuf` alone: ring never rolls (n_rows <= window) — the
        #    sorted multiset, pure insertion, no eviction bookkeeping.
        #  - `sbuf` + `buf`: general rolling window; `buf` keeps
        #    insertion order so the evicted value can be found.
        # A comparator sort per scan row is the dominant cost at fleet
        # scale, incremental maintenance is not.  +inf padding sorts
        # last, so the first `cnt` entries are real.
        if n_rows is not None and n_rows <= desc.window:
            m = _topm_size(desc.param, n_rows)
            if m is not None:
                return {"top": jnp.full((D, m), -jnp.inf),
                        "cnt": jnp.zeros(D, jnp.int32)}
            return {"sbuf": jnp.full((D, desc.window), jnp.inf),
                    "cnt": jnp.zeros(D, jnp.int32)}
        return {"buf": jnp.full((D, desc.window), jnp.inf),
                "sbuf": jnp.full((D, desc.window), jnp.inf),
                "cnt": jnp.zeros(D, jnp.int32)}
    return {}                                 # observed / mean: stateless


def _core_estimate(desc: BankDesc, st, priors, x, jnp):
    """The warm-state estimate with the cold-start chain
    state -> prior -> observation (`x=None` drops the last link — the
    lag>0 view, where the current upload has not arrived)."""
    fallback = priors if x is None else jnp.where(
        jnp.isnan(priors), x, priors)
    if desc.kind == "observed":
        return fallback if x is None else x
    if desc.kind == "mean":
        return priors
    if desc.kind == "ewma":
        return jnp.where(st["seen"], st["est"], fallback)
    # pctl: numpy-interpolation percentile read off the incrementally
    # maintained sorted state (no per-row sort).
    c = jnp.minimum(st["cnt"], desc.window).astype(jnp.float64)
    v = _unfused((desc.param / 100.0) * (c - 1.0), jnp)
    lo = jnp.clip(jnp.floor(v), 0).astype(jnp.int32)
    hi = jnp.clip(jnp.ceil(v), 0).astype(jnp.int32)
    g = v - jnp.floor(v)
    if "top" in st:
        # `top` is sorted descending: ascending rank k reads top[c-1-k].
        ci = jnp.minimum(st["cnt"], desc.window) - 1
        a = jnp.take_along_axis(st["top"], jnp.maximum(
            ci - lo, 0)[:, None], 1)[:, 0]
        b = jnp.take_along_axis(st["top"], jnp.maximum(
            ci - hi, 0)[:, None], 1)[:, 0]
    else:
        s = st["sbuf"]
        a = jnp.take_along_axis(s, lo[:, None], 1)[:, 0]
        b = jnp.take_along_axis(s, hi[:, None], 1)[:, 0]
    warm = jnp.where(
        g >= 0.5, b - _unfused((b - a) * (1.0 - g), jnp),
        a + _unfused((b - a) * g, jnp))
    return jnp.where(st["cnt"] > 0, warm, fallback)


def _core_observe(desc: BankDesc, st, x, mask, jnp):
    if desc.kind == "ewma":
        upd = jnp.where(
            st["seen"],
            _unfused((1.0 - desc.param) * st["est"], jnp)
            + _unfused(desc.param * x, jnp),
            x)
        return {"est": jnp.where(mask, upd, st["est"]),
                "seen": st["seen"] | mask}
    if desc.kind == "pctl":
        if "top" in st:
            # Bubble x down the descending top-m chain: 2m (D,) ops.
            cur = x
            cols = []
            for t in range(st["top"].shape[1]):
                col = st["top"][:, t]
                cols.append(jnp.maximum(col, cur))
                cur = jnp.minimum(col, cur)
            new_top = jnp.stack(cols, axis=1)
            return {"top": jnp.where(mask[:, None], new_top, st["top"]),
                    "cnt": st["cnt"] + mask}
        W = desc.window
        j = jnp.arange(W, dtype=jnp.int32)[None, :]
        s = st["sbuf"]
        if "buf" not in st:
            # Insert-only layout (ring never rolls): shift [i, W) right
            # by one and drop x in at its rank — the slot falling off
            # the end is still the +inf pad.
            i = jnp.sum(s < x[:, None], axis=1, dtype=jnp.int32)[:, None]
            left = jnp.concatenate([s[:, :1], s[:, :-1]], axis=1)
            new_s = jnp.where(j == i, x[:, None],
                              jnp.where(j > i, left, s))
            return {"sbuf": jnp.where(mask[:, None], new_s, s),
                    "cnt": st["cnt"] + mask}
        pos = st["cnt"] % W
        old = jnp.take_along_axis(st["buf"], pos[:, None], 1)[:, 0]
        hit = (j == pos[:, None]) & mask[:, None]
        # Sorted-buffer maintenance: drop the first occurrence of the
        # evicted value (index r — unfilled lanes evict the +inf pad),
        # insert x at its rank (i2, post-removal).  Every slot moves by
        # at most one position, so the update is selects over the two
        # shifted views — elementwise rank arithmetic, no comparator
        # sort and no gather.
        r = jnp.argmax(s == old[:, None], axis=1).astype(jnp.int32)[:, None]
        i = jnp.sum(s < x[:, None], axis=1, dtype=jnp.int32)[:, None]
        i2 = i - (r < i)
        left = jnp.concatenate([s[:, :1], s[:, :-1]], axis=1)
        right = jnp.concatenate([s[:, 1:], s[:, -1:]], axis=1)
        new_s = jnp.where(
            j == i2, x[:, None],
            jnp.where((r <= j) & (j < i2), right,
                      jnp.where((i2 < j) & (j <= r), left, s)))
        return {"buf": jnp.where(hit, x[:, None], st["buf"]),
                "sbuf": jnp.where(mask[:, None], new_s, s),
                "cnt": st["cnt"] + mask}
    return st


def _bank_init(desc: BankDesc, D: int, jnp, n_rows=None):
    st = {"core": _core_init(desc, D, jnp, n_rows)}
    if desc.lag > 0:
        st["pend"] = jnp.zeros((D, desc.lag))
        st["pcnt"] = jnp.zeros(D, jnp.int32)
    return st


def _bank_step(desc: BankDesc, st, x, valid, priors, jnp):
    """One request row through one bank: estimate (before this row's
    observation lands), then observe — through the lag ring when the
    bank serves a stale view."""
    if desc.lag == 0:
        est = _core_estimate(desc, st["core"], priors, x, jnp)
        return est, {"core": _core_observe(desc, st["core"], x, valid,
                                           jnp)}
    est = _core_estimate(desc, st["core"], priors, None, jnp)
    slot = st["pcnt"] % desc.lag
    old = jnp.take_along_axis(st["pend"], slot[:, None], 1)[:, 0]
    feed = valid & (st["pcnt"] >= desc.lag)
    core = _core_observe(desc, st["core"], old, feed, jnp)
    hit = (jnp.arange(desc.lag)[None, :] == slot[:, None]) \
        & valid[:, None]
    return est, {"core": core,
                 "pend": jnp.where(hit, x[:, None], st["pend"]),
                 "pcnt": st["pcnt"] + valid}


def _det_init(c: CtrlDesc, D: int, priors, jnp):
    st = {}
    if c.det_kind == "cusum":
        st["pos"] = jnp.zeros(D)
        st["neg"] = jnp.zeros(D)
    else:
        st["up"] = jnp.zeros(D)
        st["up_min"] = jnp.zeros(D)
        st["dn"] = jnp.zeros(D)
        st["dn_max"] = jnp.zeros(D)
    if c.fixed_scale is None:
        pre = c.scale_frac * jnp.abs(priors)
        st["sset"] = pre > 0
        st["scale"] = jnp.where(pre > 0,
                                jnp.maximum(pre, c.min_scale), 0.0)
    return st


def _det_step(c: CtrlDesc, st, r, s_obs, valid, jnp):
    """Standardize the residual, advance the two-sided statistic,
    return the (D,) alarm in {-1, 0, +1}. The statistic resets where it
    fires regardless of the controller's cooldown — exactly the python
    detectors, whose `update` self-resets."""
    st = dict(st)
    if c.fixed_scale is not None:
        z = r / c.fixed_scale
    else:
        cur = jnp.where(st["sset"], st["scale"],
                        jnp.maximum(s_obs, c.min_scale))
        z = r / cur
        new = jnp.maximum(
            _unfused((1.0 - c.scale_beta) * cur, jnp)
            + _unfused(c.scale_beta * s_obs, jnp),
            c.min_scale)
        st["scale"] = jnp.where(valid, new, st["scale"])
        st["sset"] = st["sset"] | valid
    if c.det_kind == "cusum":
        pos = jnp.maximum(0.0, st["pos"] + z - c.drift)
        neg = jnp.maximum(0.0, st["neg"] - z - c.drift)
        alarm = jnp.where(pos > c.threshold, 1,
                          jnp.where(neg > c.threshold, -1, 0))
        fired = valid & (alarm != 0)
        st["pos"] = jnp.where(valid,
                              jnp.where(fired, 0.0, pos), st["pos"])
        st["neg"] = jnp.where(valid,
                              jnp.where(fired, 0.0, neg), st["neg"])
    else:
        up = st["up"] + z - c.drift
        up_min = jnp.minimum(st["up_min"], up)
        dn = st["dn"] + z + c.drift
        dn_max = jnp.maximum(st["dn_max"], dn)
        alarm = jnp.where(up - up_min > c.threshold, 1,
                          jnp.where(dn_max - dn > c.threshold, -1, 0))
        fired = valid & (alarm != 0)
        for k, v in (("up", up), ("up_min", up_min), ("dn", dn),
                     ("dn_max", dn_max)):
            st[k] = jnp.where(valid, jnp.where(fired, 0.0, v), st[k])
    return jnp.where(valid, alarm, 0), st


_COMPILED: Dict[tuple, object] = {}


def _compile(static_desc, ctrl_desc, shards: int):
    """Build (and cache) the jitted ``run(t_mat, valid, priors)`` array
    program for one (estimator, controller, shards) configuration.
    Shapes recompile inside jax's own cache."""
    key = (static_desc, ctrl_desc, shards)
    fn = _COMPILED.get(key)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(t_mat, valid, priors):
        L, D = t_mat.shape
        if ctrl_desc is None:
            bank0 = _bank_init(static_desc, D, jnp, L)

            def step(st, row):
                x, v = row
                est, st = _bank_step(static_desc, st, x, v, priors,
                                     jnp)
                return st, {"est": est}

            _, out = lax.scan(step, bank0, (t_mat, valid))
            return out

        c = ctrl_desc
        carry0 = {
            "mon": _bank_init(c.monitor, D, jnp, L),
            "det": _det_init(c, D, priors, jnp),
            "mode": jnp.full(D, c.start, jnp.int32),
            "cool": jnp.zeros(D, jnp.int32),
            "ref": priors + jnp.zeros(D),
            "banks": [None if d is None else _bank_init(d, D, jnp, L)
                      for d in c.table],
        }

        def step(st, row):
            x, v = row
            # Tracker: pre-observation prediction, observe, post level.
            pred, mon = _bank_step(c.monitor, st["mon"], x, v, priors,
                                   jnp)
            post = _core_estimate(c.monitor, mon["core"], priors, x,
                                  jnp)
            # Detect on (obs - reference); learn scale from the tracker
            # residual (process noise, not the offset being detected).
            alarm, det = _det_step(c, st["det"], x - st["ref"],
                                   jnp.abs(x - pred), v, jnp)
            in_cool = st["cool"] > 0
            cool = jnp.where(v & in_cool, st["cool"] - 1, st["cool"])
            eff = jnp.where(v & ~in_cool, alarm, 0)
            new_mode = jnp.clip(st["mode"] + jnp.sign(eff), 0,
                                c.n_modes - 1).astype(jnp.int32)
            switched = (eff != 0) & (new_mode != st["mode"])
            down_bottom = (eff < 0) & ~switched
            # int8 event outputs: mode indices and the alarm sign fit,
            # and the stacked (L, D) outputs are copy-bound at scale.
            out = {
                "switched": switched,
                "ev_from": st["mode"].astype(jnp.int8),
                "ev_to": new_mode.astype(jnp.int8),
                "ev_alarm": eff.astype(jnp.int8),
                "ev_ref": st["ref"], "ev_level": post,
            }
            mode = jnp.where(switched, new_mode, st["mode"])
            out["mode"] = mode.astype(jnp.int8)
            banks = []
            for i, d in enumerate(c.table):
                if d is None:
                    out[f"est{i}"] = x
                    banks.append(None)
                else:
                    est, b = _bank_step(d, st["banks"][i], x, v,
                                        priors, jnp)
                    out[f"est{i}"] = est
                    banks.append(b)
            return {"mon": mon, "det": det, "mode": mode,
                    "cool": jnp.where(switched, c.cooldown, cool),
                    "ref": jnp.where(switched | down_bottom, post,
                                     st["ref"]),
                    "banks": banks}, out

        _, out = lax.scan(step, carry0, (t_mat, valid))
        return out

    if shards > 1:
        from jax.sharding import Mesh, PartitionSpec as P
        devs = jax.devices()
        if len(devs) < shards:
            raise ValueError(
                f"shards={shards} but only {len(devs)} jax devices; "
                f"call repro.utils.config.configure(host_devices="
                f"{shards}) before jax initializes (CI sets "
                f"REPRO_HOST_DEVICES)")
        mesh = Mesh(np.array(devs[:shards]), ("fleet",))
        run = jax.shard_map(run, mesh=mesh,
                            in_specs=(P(None, "fleet"), P(None, "fleet"),
                                      P("fleet")),
                            out_specs=P(None, "fleet"))
    fn = jax.jit(run)
    _COMPILED[key] = fn
    return fn


def _run_program(static_desc, ctrl_desc, packed: _Packed,
                 priors_vec: np.ndarray, shards: int):
    """Pad to the shard grid, run the jitted program under x64, strip
    the padding, and hand back numpy arrays."""
    import jax
    t_mat, valid = packed.t_mat, packed.valid
    D = t_mat.shape[1]
    pad = (-D) % shards
    if pad:
        t_mat = np.pad(t_mat, ((0, 0), (0, pad)))
        valid = np.pad(valid, ((0, 0), (0, pad)))
        priors_vec = np.pad(priors_vec, (0, pad), constant_values=1.0)
    for desc in ([static_desc] if ctrl_desc is None else
                 [ctrl_desc.monitor, *ctrl_desc.table]):
        if desc is not None and desc.kind == "mean" and np.isnan(
                priors_vec).any():
            raise ValueError("mean estimator needs a prior")
    fn = _compile(static_desc, ctrl_desc, shards)
    with jax.enable_x64(True):
        out = fn(t_mat, valid, np.asarray(priors_vec, np.float64))
        out = {k: np.asarray(v)[:, :D] if pad else np.asarray(v)
               for k, v in out.items()}
    return out


# --------------------------------------------------------------------------
# Engine entry points (called from simulate())
# --------------------------------------------------------------------------

def _assemble_events(out, packed: _Packed, mode_names: List[str],
                     device_names, dev) -> List[dict]:
    """The (L, D) switch masks back into the python engine's
    chronological event-dict list."""
    ks, ds = np.nonzero(out["switched"] & packed.valid)
    if not len(ks):
        return []
    req = packed.r_idx[ks, ds]
    o = np.argsort(req, kind="stable")
    ks, ds, req = ks[o], ds[o], req[o]
    events = []
    for k, d, r in zip(ks, ds, req):
        if dev is None:
            name = ""
        elif device_names is not None:
            name = str(device_names[d])
        else:
            name = str(d)
        events.append({
            "request": int(r), "device": name,
            "from": mode_names[int(out["ev_from"][k, d])],
            "to": mode_names[int(out["ev_to"][k, d])],
            "alarm": int(out["ev_alarm"][k, d]),
            "ref": float(out["ev_ref"][k, d]),
            "level": float(out["ev_level"][k, d])})
    return events


def scan_plan_batch(plane, rng: np.random.Generator, t_sla: float,
                    t_inputs: np.ndarray, *,
                    device_index: Optional[np.ndarray] = None,
                    prior_vec: Optional[np.ndarray] = None,
                    device_names=None, estimator_scope: str = "device",
                    realized: Optional[np.ndarray] = None,
                    prior_mean: Optional[np.ndarray] = None,
                    on_device=None, shards: int = 1):
    """`ControlPlane.plan_batch`, scan-engine edition: budget
    estimation and the adaptive controller run as the (L, D) array
    program; selection, hedging gates, fallback masks, and the RNG
    draws then go through the *shared* `finish_static` /
    `finish_adaptive` — op-for-op and draw-for-draw the python path.

    `device_index` / `prior_vec` are the fleet's integer device axis
    and per-device long-run means; None collapses to one shared column
    (no fleet, or ``estimator_scope="global"``)."""
    t_inputs = np.asarray(t_inputs, np.float64)
    n = len(t_inputs)
    dev = device_index if estimator_scope == "device" else None
    if dev is None:
        D = 1
        dev_cols = np.zeros(n, np.int64)
        priors_vec = np.array([np.nan if plane.default_prior is None
                               else float(plane.default_prior)])
    else:
        dev_cols = np.asarray(dev, np.int64)
        priors_vec = np.asarray(prior_vec, np.float64)
        D = len(priors_vec)

    if plane.controller is None:
        desc = _static_desc(plane)
        if desc is None:                      # identity: budget = obs
            t_est = t_inputs.copy()
        else:
            if desc.prior_override is not None:
                priors_vec = np.full(D, desc.prior_override)
            packed = _pack_columns(t_inputs, dev_cols, D)
            out = _run_program(desc, None, packed, priors_vec, shards)
            t_est = _unpack(packed, out["est"])
        return plane.finish_static(rng, t_sla, t_est, realized,
                                   prior_mean, on_device, n)

    cdesc = _ctrl_desc(plane)
    if dev is not None and np.isnan(priors_vec).any():
        raise ValueError("engine='scan' adaptive control needs a prior "
                         "for every device")
    packed = _pack_columns(t_inputs, dev_cols, D)
    out = _run_program(None, cdesc, packed, priors_vec, shards)
    modes_idx = _unpack(packed, out["mode"], np.int64)
    spec_order = list(dict.fromkeys(
        m.t_estimator for m in plane.controller.modes))
    series = {spec: _unpack(packed, out[f"est{i}"])
              for i, spec in enumerate(spec_order)}
    t_est = plane.compose_adaptive_estimates(series, modes_idx, n)
    events = _assemble_events(out, packed,
                              plane.controller.mode_names(),
                              device_names, dev)
    return plane.finish_adaptive(rng, t_sla, t_est, modes_idx, events,
                                 realized, prior_mean, on_device, n)


def scan_event_phase(cfg, plan, t_inputs, arrivals, exec_samples,
                     profiles, zoo, rng):
    """The request event loop, vectorized: cold starts charged at each
    model's first (non-fallback) use in request order — the same
    `zoo.ensure_hot` calls, in the same order, drawing from the same
    rng as the python loop — then closed-loop latencies as one numpy
    expression or open-loop queueing as a small `lax.scan` over the
    arrival sequence. Returns ``(lat, sel, hedges, fallbacks)``."""
    n = len(t_inputs)
    sel = plan.sel
    fb = (plan.fb_mask if plan.fb_mask is not None
          else np.zeros(n, bool))
    fallbacks = int(fb.sum())
    startup = np.zeros(n)
    live = np.flatnonzero(~fb)
    if live.size:
        # First use per model, in request order (= python's rng order).
        _, first = np.unique(sel[live], return_index=True)
        firsts = np.sort(live[first])
        for i in firsts:
            startup[i] = zoo.ensure_hot(profiles[sel[i]].name,
                                        arrivals[i], rng)
    exec_t = exec_samples[np.arange(n), np.maximum(sel, 0)] + startup
    if cfg.arrival_rate_hz <= 0:
        lat = (t_inputs + exec_t) + t_inputs   # python's add order
        queue = None
    else:
        import jax
        import jax.numpy as jnp
        from jax import lax
        hedgeable = cfg.n_servers > 1

        def step(carry, row):
            sf, h = carry
            a, e, p95g, outg, active = row
            s = jnp.argmin(sf)
            start = jnp.maximum(a, sf[s])
            do_h = active & hedgeable & (
                (p95g & (start - a > 0.05 * cfg.t_sla)) | outg)
            sf = jnp.where(active, sf.at[s].set(start + e), sf)
            return (sf, h + do_h), jnp.where(active, start - a, 0.0)

        with jax.enable_x64(True):
            (_, hedges), queue = lax.scan(
                step, (jnp.zeros(cfg.n_servers), jnp.int64(0)),
                (jnp.asarray(arrivals + t_inputs), jnp.asarray(exec_t),
                 jnp.asarray(plan.p95_gate),
                 jnp.asarray(plan.outage_gate), jnp.asarray(~fb)))
            queue = np.asarray(queue)
        lat = ((t_inputs + queue) + exec_t) + t_inputs
    hedges = 0 if queue is None else int(hedges)
    if fallbacks:
        lat = np.where(fb, plan.od_latency, lat)
        sel = np.where(fb, -1, sel)
    return lat, sel, hedges, fallbacks
