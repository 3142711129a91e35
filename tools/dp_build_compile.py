#!/usr/bin/env python
"""Compile the data-parallel build program for a described v5e 2x2.

    JAX_PLATFORMS=cpu python tools/dp_build_compile.py [rows] [config]

`config` names a file of `benchmark/configs/` (default
`criteo67-255-dp4`). The engine is laid out over 65,536 rows of the
config's generator (the same bin boundaries, so the same constants in
the program) on four virtual CPU devices, then told a shard's real
shape (`rows` over the config's `num_machines`: C and NC as the engine
reckons them on a shard's rows) and its mesh is replaced by four
described TPU devices. The shard_mapped build program is lowered from
shapes alone and compiled by the installed libtpu, which says what the
chip's compiler would refuse and how many bytes a chip holds while the
program runs (`memory_analysis()`), and which collectives it put in.
Nothing runs and no row of the config's size is made.
"""
import json
import os
import re
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

MADE = 65536


def main(argv) -> int:
    import importlib

    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset as CoreDataset
    from lightgbm_tpu.models.aligned_builder import AlignedEngine
    from lightgbm_tpu.ops.aligned import chunk_for

    name = argv[2] if len(argv) > 2 else "criteo67-255-dp4"
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        conf = json.load(f)
    rows = int(argv[1]) if len(argv) > 1 else int(conf["rows"])
    jax.config.update("jax_enable_compilation_cache", False)
    params = dict(conf["params"], metric="none", verbosity=-1,
                  tpu_grow_mode="aligned", tpu_aligned_interpret=True)
    cfg = Config.from_params(params)
    gen = importlib.import_module(
        "benchmark.generators." + conf["generator"]).Generator(
            conf["generator_params"], 1)
    x, y = gen.rows(0, MADE)
    core = CoreDataset.create_from_sample(
        gen.sample(cfg.bin_construct_sample_cnt), MADE, config=cfg)
    core.push_rows(x, label=y)
    core.finish_load()
    ds = lgb.Dataset(None, params=params)
    ds._handle = core
    gbdt = lgb.Booster(params=params, train_set=ds)._gbdt
    learner = gbdt.learner.inner
    eng = AlignedEngine(learner, gbdt.objective, interpret=False)
    nd = learner.mesh_size
    learner.n = rows
    per = learner.aligned_shard_rows
    eng.C = C = chunk_for(cfg, learner.num_features, per)
    eng.n, eng.per_shard = rows, per
    eng.NC = (per + C - 1) // C + eng.S + 2
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    eng.mesh = mesh = Mesh(np.asarray(topo.devices[:nd]), (eng.axis,))
    ins, outs = eng._specs("build")
    fn = jax.jit(jax.shard_map(eng._build_program(), mesh=mesh,
                               in_specs=ins, out_specs=outs,
                               check_vma=False), donate_argnums=(0, 1))

    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))
    ax = eng.axis
    args = (shape((nd * eng.NC, eng.W, C), jnp.int32, P(ax)),
            shape((nd * eng.NC,), jnp.int32, P(ax)),
            shape((learner.num_features,), jnp.float32, P()),
            shape((), jnp.float32, P()), shape((), jnp.bool_, P()))
    t = time.perf_counter()
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    print(json.dumps({
        "config": name, "rows": rows, "shards": nd, "rows_a_shard": per,
        "C": C, "NC": eng.NC, "W": eng.W, "S": eng.S,
        "compile_s": round(time.perf_counter() - t, 1),
        "memory": str(compiled.memory_analysis()),
        "collectives": sorted(set(re.findall(
            r"= \S+ ((?:all-reduce|all-gather|reduce-scatter)[a-z\-]*)\(",
            text))),
        "kernels": text.count('custom_call_target="tpu_custom_call"')}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
