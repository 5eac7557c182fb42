"""Production meshes. A FUNCTION (not module-level constant) so importing this
module never touches jax device state. Meshes use Auto axis types: the model
code relies on GSPMD propagation (``P.UNCONSTRAINED``, implicit broadcasts),
which Explicit axes — ``jax.make_mesh``'s default — reject."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host offers (tests / examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_tp_mesh(tp: int):
    """1-D ('model',) mesh over the first ``tp`` local devices — the serving
    engine's tensor-parallel mesh (KV-head-group sharding; see
    ``core/sharded_retrieval.TPGroupShardedRetriever``). On CPU, force
    devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    before importing jax."""
    n = len(jax.devices())
    assert n >= tp, (f"tp={tp} needs {tp} devices, have {n} "
                     "(set --xla_force_host_platform_device_count on CPU)")
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:tp]), ("model",))
