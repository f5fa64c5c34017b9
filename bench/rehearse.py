#!/usr/bin/env python3
"""Compile a cell's engine ticks for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python bench/rehearse.py [--slots N] <cell> [<cell> ...]

For each cell it builds the abstract served parameters and KV cache at the
configuration's published widths, compiles the decode tick and the fused
prefill tick of every chunk size the engine would issue (64, 128, 256),
as ``ServingEngine`` builds them, for one chip of a described ``v5e:2x2``
topology, and prints each program's ``memory_analysis()`` and the
resident bytes of parameters and cache. ``--slots`` overrides the cell's
slots, to find how many fit. Nothing runs: a compile that
passes says nothing about results or times.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def nbytes(tree) -> int:
    import jax
    import numpy as np

    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def rehearse(name: str, sharding, slots: int | None = None) -> None:
    import jax
    import jax.numpy as jnp

    import harness
    import weights as W
    from repro.core import bitlinear
    from repro.launch import server as launcher
    from repro.models import transformer as Tr
    from repro.serving import engine as E

    cell = harness.find_cell(name)
    cfg = launcher.build_config(harness.server_args(cell, 0, False))
    harness.check_config(cfg, cell.config)
    slots = slots or cell.spec["slots"]
    max_len = cell.spec["max_len"]
    sizes = tuple(s for s in sorted(cfg.prefill_chunk_sizes)
                  if s <= E.bucket_length(max_len, cfg.prefill_chunk_sizes))
    trash = E._round_up(max_len, sizes[-1])
    cache_len = trash + sizes[-1]

    def put(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    params = put(jax.eval_shape(W.served_fn(cell.config, bitlinear.pack_params),
                                jax.random.PRNGKey(0)))
    caches = put(Tr.cache_specs(cfg, slots, cache_len, cfg.dtype)[0])
    print(f"{name}: {slots} slots x {cache_len} rows; params "
          f"{nbytes(params)} B, KV cache {nbytes(caches)} B", flush=True)

    def vec(dtype, *shape):
        return jax.ShapeDtypeStruct((slots,) + shape, dtype,
                                    sharding=sharding)

    i32, b = jnp.int32, jnp.bool_
    serve = E._serve_step_cached(cfg, "packed", "auto", None)
    progs = {"decode": serve.lower(params, {"tokens": vec(i32, 1)}, caches,
                                   vec(i32), None)}
    for c in sizes:
        fused = E._fused_tick_step(
            cfg, c, mode="packed", attn_impl="auto", eos_id=-1,
            max_len=max_len, cache_len=cache_len, trash_base=trash,
            guards=True)
        progs[f"fused{c}"] = fused.lower(
            params, caches, vec(i32), vec(i32), vec(b), vec(i32), vec(i32),
            vec(b), vec(i32, c), vec(i32), vec(b), vec(i32), vec(i32), None)
    for k, low in progs.items():
        comp = low.compile()
        txt = comp.as_text()
        ma = comp.memory_analysis()
        print(f"  {k}: tpu_custom_call x{txt.count('tpu_custom_call')}; "
              f"arguments {ma.argument_size_in_bytes} B, outputs "
              f"{ma.output_size_in_bytes} B, aliased "
              f"{ma.alias_size_in_bytes} B, temps {ma.temp_size_in_bytes} B",
              flush=True)


def main(argv) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # the program picks its Pallas kernels only when it sees a TPU
    jax.default_backend = lambda: "tpu"
    slots = None
    if argv[:1] == ["--slots"]:
        slots, argv = int(argv[1]), argv[2:]
    for name in argv:
        rehearse(name, SingleDeviceSharding(topo.devices[0]), slots)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
