"""Compile each cell's decode step and largest prefill chunk for a
described TPU v5e on this host's CPU, and print what the compiler says
they need (``memory_analysis``), beside the weights and the page pool.
Nothing runs; no chip is needed.  Use it to size lanes and pages before
spending chip time.

    JAX_PLATFORMS=cpu python bench/fit.py [cell ...]
"""
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def fit(name: str, topo) -> dict:
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import harness as H
    from repro.kernels import ops
    from repro.models import model as MD

    cell = H.load_cell(name)
    cfg = H.model_config(cell.config, cell.root)
    s = cell.mix["serving"]
    dev = SingleDeviceSharding(topo.devices[0])
    # the decode step dispatches the Pallas kernel only on a TPU backend;
    # this host's backend is the CPU, so steer it as the chip would
    ops._on_tpu = lambda: True
    put = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), tree)
    params = put(jax.eval_shape(
        lambda k: MD.init_params(k, cfg), jax.random.PRNGKey(0)))
    fcfg = cfg.freeze
    stage = 3 if s["enable_freeze"] else 0     # the engine's staging slots
    state = put(jax.eval_shape(lambda: MD.init_paged_decode_state(
        cfg, s["n_lanes"], s["max_active_pages"], staging_slots=stage)))
    B = s["n_lanes"]
    i32 = lambda *sh: jax.ShapeDtypeStruct(sh, jnp.int32,  # noqa: E731
                                           sharding=dev)
    step = jax.jit(functools.partial(
        MD.decode_step_paged, cfg=cfg, freeze_cfg=fcfg,
        enable_freeze=s["enable_freeze"], reserved_slots=stage),
        donate_argnames=("state",))
    dec = step.lower(params, token=i32(B), pos=i32(B), step=i32(B),
                     tail_slot=i32(cfg.num_layers, B), state=state,
                     live=jax.ShapeDtypeStruct((B,), jnp.bool_,
                                               sharding=dev)).compile()
    sp = s["max_seq"] // 2 if cell.mix["loop"] == "open" else 8192
    sp = min(sp, 8192)
    scratch = put(jax.eval_shape(lambda: MD.init_decode_state(cfg, 1, sp)))
    C = s["prefill_chunk"]
    chunk = jax.jit(functools.partial(MD.prefill_chunk, cfg=cfg),
                    donate_argnames=("state",))
    pre = chunk.lower(params, tokens=i32(1, C), state=scratch,
                      pos0=i32()).compile()
    nbytes = lambda tree: sum(  # noqa: E731
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    out = {"cell": name, "weights_gb": nbytes(params) / 1e9,
           "pool_gb": (state.k.size + state.v.size) * 2 / 1e9,
           "prefill_scratch_gb_at_%d" % sp: nbytes(scratch) / 1e9}
    for tag, c in (("decode_step", dec), ("prefill_chunk_%d" % C, pre)):
        m = c.memory_analysis()
        out[tag] = {k: getattr(m, k) / 1e9 for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")}
        out[tag]["kernel"] = "tpu_custom_call" in c.as_text()
    return out


def main():
    import json

    from jax.experimental import topologies
    import harness as H
    names = sys.argv[1:] or [w["name"] for w in
                             H.read_json(H.ROOT / "BENCHMARK.json")[
                                 "workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for n in names:
        print(json.dumps(fit(n, topo)), flush=True)


if __name__ == "__main__":
    main()
