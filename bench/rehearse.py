"""Rehearsal compile of a cell's serving programs for a described TPU v5e,
on a machine without the chip: what chose each configuration's depth.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell> [--layers N]

Compiles, at the cell's shapes, the prefill of the longest prompt bucket
(one request) and the decode window over every slot with the KV pool in
``pinned_host`` memory, and prints each program's ``memory_analysis()``
with the device bytes one process holds besides: the bf16 weights, the
slots' device-resident decode state and the slot pool's one-request reset
template. Nothing runs and nothing is allocated; a program that would not
fit on the chip is refused here by the chip's compiler.
"""
import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def nbytes(tree) -> int:
    import jax
    import numpy as np
    return int(sum(np.prod(a.shape) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree)))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=0,
                    help="depth to rehearse (default: the file's)")
    a = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from bench.lib import harness, serve, traffic, weights
    from repro.configs.base import FreeKVConfig
    from repro.core import offload
    from repro.models import model as mdl
    from repro.serving.sampling import SamplerConfig

    jax.config.update("jax_enable_compilation_cache", False)
    cell, conf, _, _ = harness.cell_spec(a.workload)
    model = json.loads((ROOT / conf["file"]).read_text())
    if a.layers:
        model["num_hidden_layers"] = a.layers
    mix = traffic.load(cell["traffic"])
    s, f = mix["serving"], mix["serving"]["freekv"]
    cfg = serve.program_config(model)
    fkv = FreeKVConfig(method="freekv", page_size=f["page_size"],
                       budget=f["budget"], n_sink=f["n_sink"],
                       n_window=f["n_window"], tau=f["tau"],
                       sync_interval=f["sync_interval"], offload="host",
                       use_kernels=True, kernel_interpret="compiled")
    max_len, B = traffic.max_len(mix), s["slots"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]), ("x",))
    dev = NamedSharding(mesh, PartitionSpec())
    host = NamedSharding(mesh, PartitionSpec(), memory_kind="pinned_host")

    def on(tree, where=dev):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=where), tree)

    params = on(jax.eval_shape(lambda: weights.make_params(model, 0)))

    def state_shapes(batch):
        st = jax.eval_shape(lambda: mdl.init_decode_state(
            cfg, fkv, batch, max_len, jnp.bfloat16))

        def place(path, x):
            if str(getattr(path[-1], "key", "")) == "pool":
                x = jax.eval_shape(offload.to_host_format, x)
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=host)
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev)
        return jax.tree_util.tree_map_with_path(place, st)

    state = state_shapes(B)
    template = jax.eval_shape(lambda: mdl.init_decode_state(
        cfg, fkv, 1, max_len, jnp.bfloat16))
    dev_state = {k: v for k, v in jax.tree_util.tree_flatten_with_path(
        state)[0] if v.sharding.memory_kind != "pinned_host"}
    i32 = jnp.int32
    loop = on({"cur": jax.ShapeDtypeStruct((B,), i32),
               "key": jax.ShapeDtypeStruct((B, 2), jnp.uint32),
               "count": jax.ShapeDtypeStruct((B,), i32),
               "limit": jax.ShapeDtypeStruct((B,), i32),
               "eos": jax.ShapeDtypeStruct((B,), i32),
               "fin": jax.ShapeDtypeStruct((B,), jnp.bool_),
               "stop_turnover": jax.ShapeDtypeStruct((), jnp.bool_)})
    out = {"layers": model["num_hidden_layers"],
           "weights_bytes": nbytes(params),
           "slots_device_state_bytes": nbytes(list(dev_state.values())),
           "reset_template_bytes": nbytes(template)}
    window = jax.jit(lambda p, st, lp: mdl.decode_window(
        cfg, fkv, p, st, lp, sampler=SamplerConfig(0.0),
        k_max=f["sync_interval"]), donate_argnums=(1, 2)
    ).lower(params, state, loop).compile()
    longest = traffic.buckets(mix)[-1]
    toks = on({"tokens": jax.ShapeDtypeStruct((1, longest), i32)})
    prefill = jax.jit(lambda p, b: mdl.prefill(
        cfg, fkv, p, b, max_len=max_len, state_dtype=jnp.bfloat16)
    ).lower(params, toks).compile()
    for name, c in (("decode_window", window), (f"prefill_{longest}",
                                                 prefill)):
        m = c.memory_analysis()
        out[name] = {k: int(getattr(m, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "host_argument_size_in_bytes", "host_temp_size_in_bytes")}
    held = (out["weights_bytes"] + out["slots_device_state_bytes"]
            + out["reset_template_bytes"])
    pf = out[f"prefill_{longest}"]
    out["device_bytes_at_prefill"] = (held + pf["output_size_in_bytes"]
                                      + pf["temp_size_in_bytes"])
    out["device_bytes_at_decode"] = (
        held + out["decode_window"]["temp_size_in_bytes"])
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
