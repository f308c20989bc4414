#!/usr/bin/env python3
"""Compile a cell's hot programs at full size for a described TPU v5e, with
no chip attached, and print each program's memory analysis.

  JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell>
  JAX_PLATFORMS=cpu python3 bench/rehearse.py --config <file> --traffic <file>

A training cell compiles its round engine (``make_run_chunk``, one chunk);
a serving cell its largest admission prefill and its decode chunk.  A
training mix with a ``"mesh"`` compiles over that many devices of the
described ``v5e:2x2`` host, every argument in the program's placement
(``repro.sharding.rules``), and the bytes printed are each device's.  The
second form sizes a cell that is not yet in ``BENCHMARK.json`` from its
configuration and mix files.  The compiler refuses here what it would
refuse on the chip (a program that does not fit, a block shape it cannot
tile), at no chip time.  Nothing runs, so nothing here is a time.
"""
import argparse
import contextlib
import json
import math
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
# the program's mesh_from_spec builds a mesh over this process's devices;
# the described chips then take their places
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), HERE]

GB = 1e9


def report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: arguments {m.argument_size_in_bytes / GB:.3f} GB, "
          f"outputs {m.output_size_in_bytes / GB:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / GB:.3f} GB, aliased "
          f"{m.alias_size_in_bytes / GB:.3f} GB, total {total / GB:.3f} GB, "
          f"peak {m.peak_memory_in_bytes / GB:.3f} GB", flush=True)


def train_programs(cfg, mix, place):
    import jax
    import jax.numpy as jnp
    from repro.configs.base import OptimizerConfig
    from repro.core.federated import make_run_chunk
    from repro.core.lora import AdapterSet
    from repro.models.api import build_model
    from repro.sharding import rules
    import model as bmodel
    import traffic
    fam = bmodel.family(cfg)
    model = build_model(fam.program_config(cfg))
    n = mix["clients"]
    params = place(jax.eval_shape(model.init, jax.random.key(0)),
                   rules.params_sharding)
    lora = place(fam.program_lora(jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        fam.lora_shapes(cfg, mix["rank"], mix["targets"], lead=(n,)),
        is_leaf=lambda x: isinstance(x, tuple))), rules.lora_sharding)
    aset = AdapterSet(lora=lora, gamma=traffic.sfedlora_gamma(mix),
                      rank=mix["rank"], alpha=mix["alpha"])
    opt = place({"t": jax.ShapeDtypeStruct((n,), jnp.int32)},
                rules.lora_sharding)
    key = place(jax.eval_shape(lambda: jax.random.key(0)))
    round0 = place(jax.ShapeDtypeStruct((), jnp.int32))
    batches = place({"tokens": jax.ShapeDtypeStruct(
        (mix["chunk_rounds"], n, mix["local_steps"], mix["batch_per_client"],
         mix["seq_len"]), jnp.int32)}, rules.chunked_inputs_sharding)
    run_chunk = make_run_chunk(
        model, strategy=mix["aggregation"],
        opt_cfg=OptimizerConfig(name=mix["optimizer"], lr=mix["lr"]))
    yield "run_chunk", run_chunk.lower(params, aset, opt, key, round0,
                                       batches=batches)


def serve_programs(cfg, mix, place):
    import jax
    import jax.numpy as jnp
    from repro.core.lora import AdapterSet
    from repro.launch import serve
    from repro.models.api import build_model
    import model as bmodel
    fam = bmodel.family(cfg)
    model = build_model(fam.program_config(cfg))
    b = mix["max_batch"]
    mb = -(-(mix["prompt_len"] + mix["output"]["max"]) // mix["block_size"])
    params = place(jax.eval_shape(model.init, jax.random.key(0)))
    cache = place(jax.eval_shape(lambda: model.init_paged_cache(
        1 + b * mb, mix["block_size"], b)))
    lora = place(fam.program_lora(jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
        fam.lora_shapes(cfg, mix["rank"], mix["targets"],
                        lead=(mix["tenants"],)),
        is_leaf=lambda x: isinstance(x, tuple))))
    i32 = lambda *s: place(jax.ShapeDtypeStruct(s, jnp.int32))
    # what AdapterBank.requests(ids) builds, without its host-side id check
    adapters = AdapterSet(lora=lora, gamma=1.0, rank=mix["rank"],
                          batched=True, ids=i32(b))
    for g in sorted({1, b // 2, b}):
        yield f"paged_admit[{g}]", serve._jit_paged_admit(model).lower(
            params, cache, i32(g, mix["prompt_len"]), i32(g, mb), i32(g),
            i32(g * mb), AdapterSet(lora=lora, gamma=1.0, rank=mix["rank"],
                                    batched=True, ids=i32(g)))
    yield "paged_chunk", serve._jit_paged_chunk(model).lower(
        params, cache, i32(b, 1), i32(b),
        place(jax.ShapeDtypeStruct((b,), jnp.bool_)), i32(b, mb), adapters,
        steps=mix["chunk"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="a cell of BENCHMARK.json")
    ap.add_argument("--config", help="a configuration file, with --traffic")
    ap.add_argument("--traffic", help="a traffic mix file, with --config")
    args = ap.parse_args(argv)
    if bool(args.workload) == bool(args.config and args.traffic):
        ap.error("give --workload, or --config and --traffic")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)
    import harness
    import model as bmodel
    import traffic
    if args.workload:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            cell = {w["name"]: w for w in json.load(f)["workloads"]}[
                args.workload]
        cfg, mix = bmodel.load_config(cell["config"]), traffic.load(
            cell["traffic"])
    else:
        cfg = bmodel.load_config(
            os.path.basename(args.config)[:-len(".json")],
            os.path.dirname(os.path.abspath(args.config)))
        with open(args.traffic) as f:
            mix = json.load(f)
        cell = {"chips": math.prod(harness.mesh_dims(mix["mesh"]))
                if "mesh" in mix else 1}
    refused = harness.check_chips(cell, mix)
    if refused:
        print(f"rehearse: {refused}", file=sys.stderr)
        return 2
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    scope = contextlib.nullcontext()
    if "mesh" in mix:
        from repro.launch.mesh import mesh_from_spec
        from repro.sharding.specs import use_mesh
        host = mesh_from_spec(mix["mesh"])
        mesh = Mesh(np.asarray(topo.devices[:host.devices.size]).reshape(
            host.devices.shape), host.axis_names, axis_types=host.axis_types)
        scope = use_mesh(mesh)

        def place(tree, rule=None):
            shardings = (rule(tree, mesh) if rule else jax.tree.map(
                lambda _: NamedSharding(mesh, PartitionSpec()), tree))
            return jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=sh),
                tree, shardings)
    else:
        one = SingleDeviceSharding(topo.devices[0])

        def place(tree, rule=None):
            return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=one), tree)
    programs = train_programs if mix["kind"] == "train" else serve_programs
    failed = 0
    with scope:
        for name, lowered in programs(cfg, mix, place):
            try:
                report(name, lowered.compile())
            except jax.errors.JaxRuntimeError as e:
                failed += 1
                print(f"{name}: refused: {str(e).splitlines()[0]}",
                      flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
