#!/usr/bin/env python
"""Which arrays does the aligned build program copy whole?

python tools/build_program_copies.py <config> [--min-mb 64] [--rows 65536]

`<config>` names a file of `benchmark/configs/` (`criteo67-255`) or is a
path to one. The tool compiles the engine's `build` program (`build_ext`
where the objective's gradients come from outside the record) at the
config's own shape and lists, from the optimised HLO, every `copy` of at
least `--min-mb` megabytes: its shape, the computation it sits in (the
entry, a while body, a branch), its operand, the phase the program gives
it (`lightgbm_tpu/obs/phases.py`) and its source line, where the compiler
left one. A loop-carried array
that is not updated in place shows here as a copy inside the while body:
one per round (PERF.md section 6, PR 30, found the record matrix there,
14.6 ms a round at 4.5 GiB). It also counts the `move_pass` custom calls
(the build program must hold one) and prints `memory_analysis()`.

No row of the config's size is made: the learner is built over `--rows`
rows of the config's generator (the same bin boundaries, so the same
constants in the program) and told the config's row count before the
engine lays out its records, which stay zeros; the program is compiled
and never run. On a TPU that is the chip's compiler (about a minute);
anywhere else the installed libtpu compiles for a described v5e, which
gives the same HLO without the chip.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from lightgbm_tpu.obs import hlo, phases  # noqa: E402


def engine_for(config: dict, rows_made: int):
    """An AlignedEngine laid out for the config's row count over
    `rows_made` rows of its generator."""
    import importlib

    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset as CoreDataset
    from lightgbm_tpu.models.aligned_builder import AlignedEngine

    params = dict(config["params"], metric="none", verbosity=-1)
    gen = importlib.import_module(
        "benchmark.generators." + config["generator"]).Generator(
            config["generator_params"], 1)
    groups = None
    if hasattr(gen, "groups"):      # whole queries only
        bounds = np.asarray(gen.bounds)
        rows_made = int(bounds[np.searchsorted(bounds, rows_made,
                                               side="right") - 1])
        groups = gen.groups(0, rows_made)
    cfg = Config.from_params(params)
    x, y = gen.rows(0, rows_made)
    core = CoreDataset.create_from_sample(
        gen.sample(cfg.bin_construct_sample_cnt), rows_made, config=cfg)
    core.push_rows(x, label=y)
    core.finish_load(group=groups)
    ds = lgb.Dataset(None, params=params)
    ds._handle = core
    gbdt = lgb.Booster(params=params, train_set=ds)._gbdt
    learner = gbdt.learner
    learner.n = int(config["rows"])     # the layout follows the row count
    return AlignedEngine(learner, gbdt.objective, interpret=False,
                         bagged=gbdt._will_bag(),
                         bag_multiplier=gbdt._bag_multiplier,
                         bag_device=gbdt._bag_on_device)


def compile_build(eng):
    """The engine's build program, compiled for the chip (or for a
    described v5e where there is none) and not run."""
    if jax.default_backend() == "tpu":
        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)
        target = jax.devices()[0].device_kind
    else:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
        one = SingleDeviceSharding(topo.devices[0])

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
        target = "described " + topo.devices[0].device_kind
    args = [spec(eng.rec.shape, jnp.int32), spec(eng.cnts.shape, jnp.int32),
            spec((eng.learner.num_features,), jnp.float32),
            spec((), jnp.float32), spec((), jnp.bool_)]
    if eng.ext:
        args += [spec((eng.n,), jnp.float32)] * 2
    # an engine that parks the rows its bag leaves out takes where the
    # parked block begins, whether the lane is newer than the partition,
    # and the in-bag count
    park = {"park": (spec((), jnp.int32), spec((), jnp.bool_),
                     spec((), jnp.int32))} if eng.parks else {}
    program = jax.jit(eng._build_program(external_grads=eng.ext),
                      donate_argnums=(0, 1))
    return program.lower(*args, **park).compile(), target


def large_copies(text: str, min_bytes: int) -> dict:
    """{"copies": [...], "move_pass_calls": n} from optimised HLO text
    (`lightgbm_tpu/obs/hlo.py` takes it apart): each copy with the phase
    the program gives it (`obs/phases.py`) and the source line its
    `op_name` came from, where the compiler left it one."""
    instrs = hlo.instructions(text)
    # what each computation is to the one that calls it: a while's body, a
    # conditional's branch, the inside of a fusion. The round loop is the
    # while whose body holds `move_pass`
    moves = [ins.computation for ins in instrs
             if ins.opcode == "custom-call"
             and ins.name.startswith("move_pass")]
    role = hlo.roles(instrs, loop_of=moves)
    rows = {r["instruction"]: r for r in phases.rows_of("build", text)}
    copies = []
    for ins in instrs:
        if ins.opcode == "copy" and hlo.nbytes(ins.shape) >= min_bytes:
            row = rows.get(ins.name, {})
            copies.append({
                "copy": ins.name, "shape": ins.shape.split("{")[0],
                "mb": round(hlo.nbytes(ins.shape) / 1e6, 1),
                "in": hlo.place(role, ins.computation),
                "computation": ins.computation,
                "operand": "%" + (hlo.operands(ins) or ["?"])[0],
                "phase": row.get("phase"),
                "source": None if not row.get("source_file") else
                f"{os.path.relpath(row['source_file'], ROOT)}:"
                f"{row['source_line']}"})
    return {"copies": copies, "move_pass_calls": len(moves)}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--min-mb", type=float, default=64.0)
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--hlo-out", help="write the optimised HLO here")
    opts = ap.parse_args()
    path = opts.config if os.path.exists(opts.config) else os.path.join(
        ROOT, "benchmark", "configs", opts.config + ".json")
    with open(path) as f:
        config = json.load(f)
    eng = engine_for(config, opts.rows)
    compiled, target = compile_build(eng)
    hlo = compiled.as_text()
    if opts.hlo_out:
        os.makedirs(os.path.dirname(os.path.abspath(opts.hlo_out)),
                    exist_ok=True)
        with open(opts.hlo_out, "w") as f:
            f.write(hlo)
    mem = compiled.memory_analysis()
    out = large_copies(hlo, int(opts.min_mb * 1e6))
    out.update(
        config=config["name"], program="build_ext" if eng.ext else "build",
        target=target, records=list(eng.rec.shape),
        records_mb=round(int(np.prod(eng.rec.shape)) * 4 / 1e6, 1),
        memory_analysis={k: int(getattr(mem, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes") if hasattr(mem, k)})
    for c in out["copies"]:
        print(json.dumps(c), flush=True)
    print(json.dumps({k: v for k, v in out.items() if k != "copies"}),
          flush=True)


if __name__ == "__main__":
    main()
