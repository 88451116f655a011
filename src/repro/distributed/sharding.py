"""Logical-axis -> mesh-axis sharding rules (MaxText-style), with
divisibility-aware fallback to replication.

Mesh axes:
  single-pod: ("data", "model")           16 x 16
  multi-pod : ("pod", "data", "model")    2 x 16 x 16  (pod folds into DP)

Roles:
  batch      -> ("pod","data")   data parallelism
  embed      -> "data"           FSDP / ZeRO-3 weight sharding
  vocab/heads/kv_heads/ffn/experts -> "model"  tensor / expert parallelism
  seq_kv     -> "model"          flash-decode KV-cache sequence sharding
  seq_sp     -> "model"          context parallelism (q-seq) for archs whose
                                 head count does not divide the model axis
  longseq    -> ("data","model") 524k KV sharded over both axes (batch=1)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.nn.param import PSpec, map_specs


def default_rules(mesh: Mesh) -> dict:
    has_pod = "pod" in mesh.axis_names
    batch = ("pod", "data") if has_pod else ("data",)
    return {
        "batch": batch,
        "embed": ("data",),
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "ffn": ("model",),
        "experts": ("model",),
        "expert_ffn": ("model",),
        "seq_kv": ("model",),
        "seq_sp": ("model",),
        # Megatron-style sequence parallelism: the residual stream between
        # layers is sharded over "model" on the seq dim (falls back to
        # replicated automatically when S==1, i.e. decode).
        "seq_res": ("model",),
        "longseq": ("data", "model"),
        "layers": (),
        None: (),
    }


def _axis_size(mesh: Mesh, names: Tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[n] for n in names])) if names else 1


def resolve_spec(mesh: Mesh, shape: Tuple[int, ...],
                 axes: Tuple[Optional[str], ...], rules: Optional[dict] = None,
                 ) -> P:
    """PartitionSpec for ``shape`` given logical ``axes``; any dim that is not
    evenly divisible by its mesh-axis extent falls back to replication (this
    handles e.g. 36 attention heads on a 16-wide model axis)."""
    rules = rules or default_rules(mesh)
    used: set = set()
    parts = []
    for dim, ax in zip(shape, axes):
        mesh_axes = tuple(a for a in rules.get(ax, ()) if a not in used)
        if mesh_axes and dim % _axis_size(mesh, mesh_axes) == 0:
            parts.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
            used.update(mesh_axes)
        else:
            parts.append(None)
    return P(*parts)


def spec_sharding(mesh: Mesh, spec: PSpec, rules: Optional[dict] = None) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(mesh, spec.shape, spec.axes, rules))


def tree_shardings(mesh: Mesh, spec_tree, rules: Optional[dict] = None):
    """Spec tree -> NamedSharding tree (for in_shardings / out_shardings)."""
    return map_specs(lambda s: spec_sharding(mesh, s, rules), spec_tree)


def tree_abstract(mesh: Mesh, spec_tree, dtype, rules: Optional[dict] = None):
    """Spec tree -> ShapeDtypeStruct tree with shardings (no allocation)."""

    def mk(s: PSpec):
        return jax.ShapeDtypeStruct(s.shape, dtype, sharding=spec_sharding(mesh, s, rules))

    return map_specs(mk, spec_tree)


def logical(mesh_or_none, *axes: Optional[str]):
    """Activation PartitionSpec from logical names (for sharding constraints).
    Usage: ``with_sharding_constraint(x, logical(mesh, "batch", None, "heads", None))``
    Divisibility fallback is NOT applied here (activation dims are chosen
    divisible by construction); unknown names map to None."""
    mesh = mesh_or_none
    rules = default_rules(mesh)
    parts = []
    used: set = set()
    for ax in axes:
        mesh_axes = tuple(a for a in rules.get(ax, ()) if a not in used)
        if mesh_axes:
            parts.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
            used.update(mesh_axes)
        else:
            parts.append(None)
    return P(*parts)


def activation_sharding(mesh: Mesh, *axes: Optional[str]) -> NamedSharding:
    return NamedSharding(mesh, logical(mesh, *axes))


# ---------------------------------------------------------------------------
# Data-parallel GNN mesh (HitGNN multi-device trainer)
# ---------------------------------------------------------------------------

def make_data_mesh(num_devices: int) -> Mesh:
    """A 1-D ``("data",)`` mesh over the first ``num_devices`` jax devices —
    the multi-FPGA platform the sharded GNN trainer maps the LoadBalancer's
    per-device batch slots onto. Raises with the simulated-device escape
    hatch spelled out when the process doesn't have enough devices."""
    avail = jax.device_count()
    if num_devices < 1:
        raise ValueError(f"num_devices must be >= 1, got {num_devices}")
    if avail < num_devices:
        backend = jax.default_backend()
        hint = (f"; on a CPU host simulate devices with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={num_devices} "
                f"(set BEFORE jax is imported)" if backend == "cpu" else "")
        raise ValueError(
            f"data-parallel mesh needs {num_devices} devices but this "
            f"process has {avail} {backend} device(s){hint}")
    return Mesh(np.array(jax.devices()[:num_devices]), ("data",))


def require_data_axis(mesh: Mesh, num_devices: int) -> None:
    """Validate a user-supplied mesh against the trainer's device count:
    the mesh must carry a ``"data"`` axis whose extent equals
    ``num_devices`` (one mesh slot per LoadBalancer device slot). Before
    this check, an oversized ``num_devices`` silently trained zero-weight
    fill batches on phantom devices."""
    if "data" not in mesh.axis_names:
        raise ValueError(
            f"trainer mesh must have a 'data' axis; got axes "
            f"{tuple(mesh.axis_names)}")
    extent = int(mesh.shape["data"])
    if extent != num_devices:
        raise ValueError(
            f"num_devices={num_devices} does not match the mesh's 'data' "
            f"axis extent {extent}: the sharded step places batch slot d "
            f"on mesh device d, so the counts must agree (resize the mesh "
            f"or pass num_devices={extent})")


# ---------------------------------------------------------------------------
# Ambient mesh context: model code calls ``shard(x, "batch", None, "heads")``
# which is an identity when no mesh is active (CPU smoke tests), and a
# with_sharding_constraint under the launcher/dry-run mesh.
# ---------------------------------------------------------------------------

_MESH_CTX: list = []


class use_mesh:
    """Context manager installing ``mesh`` as the ambient sharding context."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh

    def __enter__(self):
        _MESH_CTX.append(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        _MESH_CTX.pop()
        return False


def current_mesh() -> Optional[Mesh]:
    return _MESH_CTX[-1] if _MESH_CTX else None


def shard(x, *axes: Optional[str]):
    """Sharding constraint by logical axis names; no-op without a mesh.
    Dims whose size does not divide the target axes are replicated."""
    mesh = current_mesh()
    if mesh is None:
        return x
    rules = default_rules(mesh)
    parts = []
    used: set = set()
    for dim, ax in zip(x.shape, axes):
        mesh_axes = tuple(a for a in rules.get(ax, ()) if a not in used)
        if mesh_axes and dim % _axis_size(mesh, mesh_axes) == 0:
            parts.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
            used.update(mesh_axes)
        else:
            parts.append(None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))
