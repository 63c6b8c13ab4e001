"""Dispatch layer for the fused interval fast path.

Routing (resolved once per process via ``kernels/_backend``):

  * TPU backend           -> compiled Pallas kernels (kernel.py);
  * ``REPRO_FORCE_INTERPRET`` off-TPU -> interpret-mode Pallas kernels —
    the validation route the kernel-vs-ref checks pin on CPU hosts (on a
    TPU the variable is an error, see ``kernels/_backend``);
  * any other backend     -> the fused jnp references (ref.py).

``tier_migrate`` keeps a lane's page row and plans in SMEM; shapes past
that budget (``kernel.tier_migrate_fits``) take the jnp reference on
every backend.  The other three kernels take any n: a row past one VMEM
block is walked in page blocks, a layout each kernel picks from the
shape alone (``kernel._blocks``).

The references are the kernels' bitwise contract, so the scan engine's
CRN equivalence guarantees hold on every route.  Unlike the other
``kernels/*/ops.py`` wrappers there is no per-call ``use_kernel`` flag:
the scan engine toggles the whole fused path at a higher level
(``use_interval_kernel``), and these ops always take the best route for
the backend.
"""
from __future__ import annotations

from repro.kernels._backend import force_interpret, interpret_mode
from repro.kernels.interval_step import kernel, ref


def _pallas() -> bool:
    """Route to the Pallas kernel (compiled on TPU, interpret if forced)?"""
    return force_interpret() or not interpret_mode()


def topk_mask(x, k: int):
    """Exact top-k bool mask of [B, n] rows (``lax.top_k`` tie rule)."""
    if _pallas():
        return kernel.topk_mask_kernel(x, k, interpret=interpret_mode())
    return ref.topk_mask_ref(x, k)


def tier_migrate(tier, promote, demote, caps):
    """Lane-batched hop-chain migrations; see simjax.apply_tier_migrations.

    Contract: valid (non ``-1``) entries within each lane's plan are
    unique page indices (the padded-index contract) — the sequential
    kernel and the vectorized reference only coincide under it.
    """
    if _pallas() and kernel.tier_migrate_fits(
            tier.shape[1], promote.shape[1], demote.shape[1]):
        return kernel.tier_migrate_kernel(tier, promote, demote, caps,
                                          interpret=interpret_mode())
    return ref.tier_migrate_ref(tier, promote, demote, caps)


def interval_account(mach, true, tier, mig_up, mig_down, oracle, k: int):
    """Fused interval accounting + oracle recall over lane-batched rows;
    ``mach`` is a lane-batched TieredMachineSpec."""
    if _pallas():
        return kernel.interval_account_kernel(
            mach.lat_ns, mach.bw_read, mach.bw_write, mach.mlp, true, tier,
            mig_up, mig_down, oracle, k, interpret=interpret_mode())
    return ref.interval_account_ref(mach, true, tier, mig_up, mig_down,
                                    oracle, k)


def ewma_score_update(ewma_s, ewma_l, counts, *, alpha_s, alpha_l, w_s,
                      w_l, use_kernel: bool = True):
    """Lane-batched dual-EWMA + hotness score ([B, n] arrays; params
    scalar or [B]).  ``use_kernel=False`` pins the jnp reference — the
    escape hatch ``ARMSConfig.use_score_kernel`` flips at config level."""
    if use_kernel and _pallas():
        return kernel.ewma_update_kernel(
            ewma_s, ewma_l, counts, alpha_s=alpha_s, alpha_l=alpha_l,
            w_s=w_s, w_l=w_l, interpret=interpret_mode())
    return ref.ewma_score_update_ref(
        ewma_s, ewma_l, counts, alpha_s=alpha_s, alpha_l=alpha_l,
        w_s=w_s, w_l=w_l)
