"""ETF finish-time search kernels (TPU Pallas) — the paper's own hot spot.

Algorithm 1's inner search computes FT[r, p] = max(avail[r, p], free[p],
now) + exec[r, p] over (ready tasks x PEs) and takes the argmin. On the
DSSoC this runs on a Cortex-A53 in ~65 ns; the TPU-native adaptation is a
dense masked min-reduction:

  * PE axis padded to the 128-lane VPU width, one [R, Pp] tile per
    scenario, grid = (n_scenarios,);
  * the argmin is two 2-D reductions on that tile — the minimum, then the
    smallest flat index (`r * Pp + p`, from 2-D iotas) whose entry equals
    it — so there is no gather and no scalar store;
  * every operand is 3-D with the scenario axis leading, so each block
    spans its array's full last two dimensions (the (8, 128) tiling rule
    never applies); scalars travel as [S, 1, 1], per-slot masks as
    [S, R, 1], and each result leaves as a lane-wide [1, 128] row.

inf entries (PE cannot run the task type / empty ready slots) never win.

Two kernels serve the simulator's decision hot path (dispatched by
`ops.py`, knob `REPRO_SIM_KERNELS`):

  * `etf_ft_search_masked` — the scenario-batched decision search with
    per-lane `slot_ok` / `pe_alive` masks and a degraded-mode feasibility
    flag. The tie-break contract is the simulator's: the FIRST global
    minimum of the flattened [R, P] finish-time matrix wins, exactly as
    `jnp.argmin` over the inf-masked matrix does, so the kernel-backed
    decision path is bit-exact against the inline jnp path.
  * `push_rows` — the push-time availability rows: for each newly-ready
    task the max over its predecessors of (pred finish + NoC transfer
    when the predecessor ran on a different cluster), fused over the
    [K, MP, P] contribution tensor in one pass.

`etf_ft_search` is the unmasked search: the masked kernel with every slot
and PE enabled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BIG = 3.4e38
LANES = 128         # VPU lane width: the PE axis pads up to this

# One grid step of the search kernel owns a [R, Pp] block. Interpret mode
# evaluates the grid with a Python interpreter, so its cost scales with
# the total number of block cells, not the batch count — the budget below
# is 64 grid steps of the default [64, 128] block, which reproduces the
# old `B > 64` bailout at that geometry instead of hard-coding a batch
# count that silently lies for other block shapes.
MAX_INTERPRET_CELLS = 64 * 64 * LANES


def _pad_lanes(p: int) -> int:
    return max(LANES, -(-p // LANES) * LANES)


def _lead(shape):
    """BlockSpec for one scenario's slice of a [S, *shape] operand."""
    return pl.BlockSpec((1,) + shape, lambda b: (b,) + (0,) * len(shape))


def _min2d(x):
    """Minimum of a 2-D tile, kept as a [1, 1] vector."""
    return jnp.min(jnp.min(x, axis=1, keepdims=True), axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# scenario-batched masked decision search
# ---------------------------------------------------------------------------
def _etf_masked_kernel(avail_ref, free_ref, exec_ref, now_ref, sok_ref,
                       alive_ref, ft_ref, idx_ref):
    avail = avail_ref[0]                       # [R, Pp]
    free = free_ref[0]                         # [1, Pp]
    exec_t = exec_ref[0]                       # [R, Pp]
    now = now_ref[0]                           # [1, 1]
    sok = sok_ref[0]                           # [R, 1] f32 0/1
    alive = alive_ref[0]                       # [1, Pp] f32 0/1
    ft = jnp.maximum(jnp.maximum(avail, free), now) + exec_t
    ok = (sok > 0) & (alive > 0) & jnp.isfinite(ft)
    ft = jnp.where(ok, ft, BIG)
    R, Pp = ft.shape
    mn = _min2d(ft)                            # [1, 1]
    flat = (jax.lax.broadcasted_iota(jnp.int32, (R, Pp), 0) * Pp
            + jax.lax.broadcasted_iota(jnp.int32, (R, Pp), 1))
    # first global minimum: the smallest flat index attaining `mn`
    idx = _min2d(jnp.where(ft == mn, flat, R * Pp))
    ft_ref[0] = jnp.broadcast_to(mn, (1, LANES))
    idx_ref[0] = jnp.broadcast_to(idx, (1, LANES))


@functools.partial(jax.jit, static_argnames=("interpret",))
def etf_ft_search_masked(avail, free, exec_t, now, slot_ok, pe_alive, *,
                         interpret=False):
    """Scenario-batched masked search: avail/exec_t [S, R, P], free [S, P],
    now [S], slot_ok [S, R] bool, pe_alive [S, P] bool.

    Returns (ft_min [S], slot [S], pe [S], feasible [S]): the first global
    minimum of the masked finish-time matrix per scenario (identical index
    to `jnp.argmin` over the inf-masked matrix — slot 0 / pe 0 when every
    candidate is masked, in which case `feasible` is False).
    """
    S, R, P = avail.shape
    Pp = _pad_lanes(P)
    pad = ((0, 0), (0, 0), (0, Pp - P))
    avail_p = jnp.pad(avail, pad, constant_values=jnp.inf)
    exec_p = jnp.pad(exec_t, pad, constant_values=jnp.inf)
    free_p = jnp.pad(free[:, None, :], pad, constant_values=jnp.inf)
    alive_p = jnp.pad(pe_alive.astype(jnp.float32)[:, None, :], pad)
    sok = slot_ok.astype(jnp.float32)[:, :, None]

    ft, idx = pl.pallas_call(
        _etf_masked_kernel,
        grid=(S,),
        in_specs=[_lead((R, Pp)), _lead((1, Pp)), _lead((R, Pp)),
                  _lead((1, 1)), _lead((R, 1)), _lead((1, Pp))],
        out_specs=[_lead((1, LANES)), _lead((1, LANES))],
        out_shape=[jax.ShapeDtypeStruct((S, 1, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((S, 1, LANES), jnp.int32)],
        interpret=interpret,
    )(avail_p, free_p, exec_p, now[:, None, None], sok, alive_p)

    ft_min = ft[:, 0, 0]
    flat_idx = idx[:, 0, 0]
    return ft_min, flat_idx // Pp, flat_idx % Pp, ft_min < BIG


@functools.partial(jax.jit, static_argnames=("interpret",))
def etf_ft_search(avail, free, exec_t, now, *, interpret=False):
    """avail [B, R, P], free [B, P], exec_t [B, R, P], now [B].
    Returns (ft_min [B], slot [B], pe [B]): the masked search with every
    slot and PE enabled."""
    B, R, P = avail.shape
    ft_min, slot, pe, _ = etf_ft_search_masked(
        avail, free, exec_t, now, jnp.ones((B, R), bool),
        jnp.ones((B, P), bool), interpret=interpret)
    return ft_min, slot, pe


# ---------------------------------------------------------------------------
# push-time availability rows (the [K, MP, P] NoC-contribution max)
# ---------------------------------------------------------------------------
def _push_kernel(pfin_ref, cost_ref, pcl_ref, pv_ref, pecl_ref, base_ref,
                 out_ref):
    pfin = pfin_ref[0]                         # [K, MP]
    cost = cost_ref[0]                         # [K, MP]
    pcl = pcl_ref[0]                           # [K, MP] f32 cluster ids
    pv = pv_ref[0]                             # [K, MP] f32 0/1
    pecl = pecl_ref[0]                         # [Pp] f32 cluster ids
    base = base_ref[0]                         # [K, 1]
    cross = (pcl[:, :, None] != pecl[None, None, :]).astype(jnp.float32)
    contrib = jnp.where(pv[:, :, None] > 0,
                        pfin[:, :, None] + cost[:, :, None] * cross,
                        -BIG)                  # [K, MP, Pp]
    out_ref[0] = jnp.maximum(contrib.max(axis=1), base)


@functools.partial(jax.jit, static_argnames=("interpret",))
def push_rows(pfin, cost, pcl, pv, pe_cluster, bases, *, interpret=False):
    """Scenario-batched push-time rows: pfin/cost/pcl/pv [S, K, MP]
    (pred finish, NoC transfer cost, pred cluster, validity), pe_cluster
    [P], bases [S, K]. Returns rows [S, K, P]:

      rows[s, k, p] = max(max_m over valid preds of
                          (pfin + cost * (pcl != cluster(p)))), bases[s, k])

    exactly the simulator's `_avail_rows` contribution max.
    """
    S, K, MP = pfin.shape
    P = pe_cluster.shape[0]
    Pp = _pad_lanes(P)
    pecl = jnp.pad(pe_cluster.astype(jnp.float32), (0, Pp - P),
                   constant_values=-1.0)[None, :]

    out = pl.pallas_call(
        _push_kernel,
        grid=(S,),
        in_specs=[_lead((K, MP))] * 4 + [
            pl.BlockSpec((1, Pp), lambda b: (0, 0)),
            _lead((K, 1)),
        ],
        out_specs=_lead((K, Pp)),
        out_shape=jax.ShapeDtypeStruct((S, K, Pp), jnp.float32),
        interpret=interpret,
    )(pfin, cost, pcl.astype(jnp.float32), pv.astype(jnp.float32), pecl,
      bases[:, :, None])
    return out[:, :, :P]
