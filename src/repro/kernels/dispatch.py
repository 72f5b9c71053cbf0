"""Runtime dispatch between the Pallas kernels and their XLA references.

One switch decides how the three hot-path primitives execute — the fused
GRU cell (`repro.kernels.fused_gru`), the bipartite GraphSAGE round
(`repro.kernels.bipartite`) and the water-filling masked row-min
(`repro.kernels.waterfill`). Three modes:

    pallas      compiled Pallas kernels. Requires a TPU; requesting it on
                any other platform raises, so a run that asked for the
                compiled kernels can never quietly execute something else.
    xla         the pure-jnp reference math (segment-sum GNN, unfused
                GRU, jnp row-min). The fastest choice on CPU.
    interpret   the Pallas kernels under the interpreter on any platform
                (same kernels, run through the Pallas interpreter lowered
                to plain XLA ops). CPU tests request it explicitly to
                exercise the kernel code paths without a TPU.

Resolution order: a concrete caller-requested mode (e.g. a pinned
``M4Config.kernel_mode``) beats the ``REPRO_KERNELS`` environment
variable (which fills in for the default ``None``) beats the platform
probe (TPU -> "pallas", otherwise -> "xla"). Explicit code wins over the
environment so that a mode pinned at backend construction, or training's
forced differentiable "xla" path, stays in force — execution path and
cached fingerprint cannot drift apart if the env var changes
mid-process.

The resolved mode must end up in every jit cache key that depends on it,
or flipping ``REPRO_KERNELS`` between calls would silently reuse a stale
executable. Entry points therefore pin the mode *before* tracing:
`repro.core.simulate` canonicalizes ``M4Config.kernel_mode`` (a static
jit argument) via :func:`canonicalize_cfg`, and `repro.core.flowsim_fast`
threads the resolved mode as a static argument. Backend fingerprints
(`repro.sim.backends`) include the resolved mode for the same reason:
cached sweep results are only valid for the kernel path that produced
them.
"""
from __future__ import annotations

import dataclasses
import os

MODES = ("pallas", "xla", "interpret")
ENV_VAR = "REPRO_KERNELS"


def resolve_mode(requested: str | None = None) -> str:
    """Concrete execution mode from request / env override / platform.

    `requested` is typically ``M4Config.kernel_mode``. A concrete request
    wins: an entry point that pinned a mode (a canonicalized backend cfg,
    or training forcing the differentiable "xla" path) is not silently
    re-routed by the environment later — that would desynchronize cached
    fingerprints from the executed path. ``REPRO_KERNELS`` fills in when
    the request is None (every default construction), then the platform
    probe. Returns one of "pallas" (TPU only), "xla", "interpret".
    Raises ValueError when "pallas" is requested off-TPU.
    """
    if requested is None:
        env = os.environ.get(ENV_VAR, "").strip().lower() or None
        if env is not None and env not in MODES:
            raise ValueError(
                f"{ENV_VAR}={env!r} invalid; choose one of {MODES}")
        requested = env
    if requested is None:
        requested = "pallas" if _platform() == "tpu" else "xla"
    if requested not in MODES:
        raise ValueError(
            f"kernel mode {requested!r} invalid; choose one of {MODES}")
    if requested == "pallas" and _platform() != "tpu":
        raise ValueError(
            f"kernel mode 'pallas' needs a TPU, but JAX runs on "
            f"{_platform()!r}; request 'interpret' to run the Pallas "
            "kernels under the interpreter, or 'xla'")
    # count resolutions per concrete mode so an obs snapshot shows which
    # kernel path a run actually dispatched (lazy import: dispatch must
    # stay importable before the obs package loads)
    from ..obs.registry import get_registry, labeled
    get_registry().inc(labeled("kernels.dispatch", mode=requested))
    return requested


def _platform() -> str:
    import jax
    return jax.default_backend()


def canonicalize_cfg(cfg):
    """Pin ``cfg.kernel_mode`` to its resolved concrete mode.

    `cfg` is any frozen dataclass with a ``kernel_mode`` field (M4Config).
    Jitted entry points take cfg as a static argument, so pinning the mode
    here puts it in the compile cache key — changing ``REPRO_KERNELS``
    between calls retraces instead of reusing a stale kernel path.
    """
    return dataclasses.replace(cfg, kernel_mode=resolve_mode(cfg.kernel_mode))


# ------------------------------------------------------------- primitives
def gru_cell(p, x, h, *, mode: str):
    """GRU cell on params dict {"wi","wh","bi","bh"} (repro.nn layout)."""
    if mode == "xla":
        from ..nn.layers import gru_cell as gru_ref
        return gru_ref(p, x, h)
    from .fused_gru.ops import gru_cell as gru_fused
    interp = mode != "pallas"
    # interpret mode lowers to XLA anyway — small tiles beat MXU alignment
    return gru_fused(x, h, p["wi"], p["wh"], p["bi"], p["bh"],
                     tile_b=8 if interp else 128, interpret=interp)


def gru_cell_pair(p_f, p_l, x_f, h_f, x_l, h_l, *, mode: str):
    """Advance the flow GRU and the link GRU of one stage together.

    In "xla" mode the two cells are fused into one block-structured pair of
    matmuls: inputs are laid out [x_f | 0] / [0 | x_l] over stacked weight
    matrices, so XLA runs 2 GEMMs + one set of gate nonlinearities instead
    of 4 GEMMs + two — the event step is op-dispatch-bound on CPU, and the
    zero blocks change nothing numerically (x + 0·w = x). Pallas modes
    keep the per-cell fused kernel (each cell is already one kernel call).
    """
    if mode != "xla":
        return (gru_cell(p_f, x_f, h_f, mode=mode),
                gru_cell(p_l, x_l, h_l, mode=mode))
    import jax
    import jax.numpy as jnp
    Bf, Df = x_f.shape
    Bl, Dl = x_l.shape
    H = h_f.shape[1]
    B = Bf + Bl
    x = jnp.zeros((B, Df + Dl), x_f.dtype)
    x = x.at[:Bf, :Df].set(x_f).at[Bf:, Df:].set(x_l)
    h = jnp.zeros((B, 2 * H), h_f.dtype)
    h = h.at[:Bf, :H].set(h_f).at[Bf:, H:].set(h_l)
    # weight stacks are loop-invariant -> hoisted out of the event scan
    wi = jnp.concatenate([p_f["wi"], p_l["wi"]], 0)        # (Df+Dl, 3H)
    wh = jnp.concatenate([p_f["wh"], p_l["wh"]], 0)        # (2H, 3H)
    bi = jnp.concatenate([jnp.broadcast_to(p_f["bi"], (Bf, 3 * H)),
                          jnp.broadcast_to(p_l["bi"], (Bl, 3 * H))], 0)
    bh = jnp.concatenate([jnp.broadcast_to(p_f["bh"], (Bf, 3 * H)),
                          jnp.broadcast_to(p_l["bh"], (Bl, 3 * H))], 0)
    gi = x @ wi + bi
    gh = h @ wh + bh
    ir, iz, in_ = jnp.split(gi, 3, axis=-1)
    hr, hz, hn = jnp.split(gh, 3, axis=-1)
    r = jax.nn.sigmoid(ir + hr)
    z = jax.nn.sigmoid(iz + hz)
    n = jnp.tanh(in_ + r * hn)
    hcat = jnp.concatenate([h_f, h_l], 0)
    out = (1.0 - z) * n + z * hcat
    return out[:Bf], out[Bf:]


def gnn_rounds(layers, f, l, edge_f, edge_l, edge_mask, num_links, *,
               mode: str):
    """Multi-round bipartite GraphSAGE (m4's spatial model)."""
    if mode == "xla":
        import jax.numpy as jnp
        from .bipartite.ref import bipartite_rounds_matmul
        # incidence built once per event with one-hot matmuls (no scatter),
        # then every round is dense matmuls — the kernel's formulation run
        # by XLA; segment-sum survives as the oracle in bipartite/ref.py
        SF, SL = f.shape[0], l.shape[0]
        fo = (edge_f[:, None]
              == jnp.arange(SF, dtype=jnp.int32)[None, :]).astype(f.dtype)
        lo = (edge_l[:, None]
              == jnp.arange(SL, dtype=jnp.int32)[None, :]).astype(f.dtype) \
            * edge_mask[:, None]
        return bipartite_rounds_matmul(layers, f, l, fo.T @ lo)
    from .bipartite.ops import bipartite_rounds
    return bipartite_rounds(layers, f, l, edge_f, edge_l, edge_mask,
                            interpret=mode != "pallas")


def masked_rowmin(a, share, *, mode: str):
    """Per-flow bottleneck share: min over the flow's links of `share`."""
    if mode == "xla":
        from .waterfill.ref import masked_rowmin_ref
        return masked_rowmin_ref(a, share)
    from .waterfill.ops import masked_rowmin as rowmin_pallas
    return rowmin_pallas(a, share, interpret=mode != "pallas")
