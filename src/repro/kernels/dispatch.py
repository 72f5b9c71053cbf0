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

The kernels take their weights padded to MXU-aligned widths.
:func:`stage_params` builds that layout once per call, before an event
scan, and the primitives below read it through :func:`kernel_params`.
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


# ------------------------------------------------------------ staging
STAGED = "_kernel"
GRUS = ("gru1", "gruA", "gru2", "gruB")


def stage_params(params, mode: str):
    """m4 params with the GRU and GNN weights also in the kernels' layout.

    In "pallas" and "interpret" modes, returns `params` with one more
    entry, ``params[STAGED]``: each of `GRUS` as `fused_gru.ops.stage_gru`
    lays it out and "gnn" as a list of `bipartite.ops.stage_round`s. The
    original entries stay. In "xla" mode, returns `params` unchanged.

    The weights do not change inside an event scan, so its entry point
    stages once, before the loop: the per-event step then hands the staged
    arrays straight to the kernels (see `kernel_params`). Staging inside
    the loop body would rebuild ~26 MB of padded weights every event at
    the paper's widths; XLA does not hoist such padding out of a loop.
    """
    if mode == "xla":
        return params
    from ..obs.registry import get_registry, labeled
    from .bipartite.ops import stage_round
    from .fused_gru.ops import stage_gru
    get_registry().inc(labeled("kernels.staged", mode=mode))
    staged = {name: stage_gru(**params[name]) for name in GRUS}
    staged["gnn"] = [stage_round(layer["wf"]["w"], layer["wl"]["w"],
                                 layer["wf"]["b"], layer["wl"]["b"])
                     for layer in params["gnn"]]
    return {**params, STAGED: staged}


def kernel_params(params, mode: str):
    """The GRU and GNN weights as the primitives below take them in
    `mode`: `params` in "xla" mode, else the tree `stage_params` added."""
    if mode == "xla":
        return params
    if STAGED not in params:
        raise ValueError(
            f"kernel mode {mode!r} takes the weights staged once per call: "
            "pass dispatch.stage_params(params, mode)")
    return params[STAGED]


# ------------------------------------------------------------- primitives
def gru_cell(p, x, h, *, mode: str):
    """GRU cell on params dict {"wi","wh","bi","bh"}: the repro.nn layout
    in "xla" mode, else the kernel layout of `stage_params`."""
    if mode == "xla":
        from ..nn.layers import gru_cell as gru_ref
        return gru_ref(p, x, h)
    from .fused_gru.ops import gru_cell_staged
    interp = mode != "pallas"
    # interpret mode lowers to XLA anyway — small tiles beat MXU alignment
    return gru_cell_staged(x, h, p, tile_b=8 if interp else 128,
                           interpret=interp)


def gru_cell_pair(p_f, p_l, x_f, h_f, x_l, h_l, *, mode: str):
    """Advance the flow GRU and the link GRU of one stage together.

    In "xla" mode the two cells are fused into one block-structured pair of
    matmuls: inputs are laid out [x_f | 0] / [0 | x_l] over stacked weight
    matrices, so XLA runs 2 GEMMs + one set of gate nonlinearities instead
    of 4 GEMMs + two — the event step is op-dispatch-bound on CPU, and the
    zero blocks change nothing numerically (x + 0·w = x). Pallas modes
    keep the per-cell fused kernel (each cell is already one kernel call),
    on weights in the `stage_params` layout.
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
    """Multi-round bipartite GraphSAGE (m4's spatial model). `layers`
    are `params["gnn"]` in "xla" mode, else their `stage_params` layout."""
    if mode == "xla":
        from .bipartite.ref import bipartite_rounds_matmul, incidence_from_edges
        # the incidence, built once per event by the builder the Pallas
        # path shares (one-hot contraction, no scatter); then every round
        # is dense matmuls — the kernel's formulation run by XLA.
        # segment-sum survives as the oracle in bipartite/ref.py
        m = incidence_from_edges(edge_f, edge_l, edge_mask, f.shape[0],
                                 l.shape[0])
        return bipartite_rounds_matmul(layers, f, l, m)
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
