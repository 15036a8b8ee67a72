"""The system under test: a ``MetaLearner`` built through the public API,
with the arguments ``repro.launch.train.build`` passes for the same flags.

``repro`` is imported from ``<checkout>/src``; a checkout without it has no
system to measure, and ``import_program`` raises.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Any, Dict

ROOT = Path(__file__).resolve().parents[2]


def import_program():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise RuntimeError(f"the program is not in this checkout: no {src / 'repro'}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro  # noqa: F401


def arch_config(config: Dict[str, Any]):
    """The registry's configuration with the file's ``changed`` keys, at the
    policy's compute dtype. Every key of the file that names a size of the
    configuration must equal it; the others (source, notes, what was
    assumed) describe it."""
    import dataclasses

    from repro import configs, scale

    cfg = configs.get_config(config["registry"]).replace(**config["changed"])
    cfg = cfg.replace(dtype=scale.ScaleConfig(policy=config["policy"]).resolve().compute_dtype)
    # the registry's own citation and name describe it; every other field is a size
    fields = {f.name for f in dataclasses.fields(cfg)} - {"source", "name"}
    for key, want in config.items():
        if key not in fields:
            continue
        have = getattr(cfg, key)
        if have != want:
            raise ValueError(f"config {config['registry']}: {key} is {have!r} in the program, "
                             f"{want!r} in the configuration file")
    return cfg


def make_mesh(chips: int):
    """``launch.mesh.make_host_mesh`` over the first ``chips`` devices."""
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh((chips, 1), ("data", "model"), devices=jax.devices()[:chips],
                         axis_types=(AxisType.Auto, AxisType.Auto))


def build_learner(config: Dict[str, Any], mix: Dict[str, Any], chips: int):
    """(cfg, model, learner) as ``train.build`` makes them for ``--arch <registry>
    --unroll <unroll> --method <method> --base-lr --meta-lr
    --precision <policy> [--manual-collectives]``, without initialising
    its state."""
    from repro import api, scale
    from repro.core import problems
    from repro.models import Model

    cfg = arch_config(config)
    model = Model(cfg)
    spec = problems.make_data_optimization_spec(
        model.classifier_per_example if cfg.family == "encoder" else model.per_example,
        reweight=True,
    )
    learner = api.MetaLearner(
        spec, scale=scale.ScaleConfig(policy=config["policy"], microbatch=1),
        base_opt=mix["base_opt"], base_lr=mix["base_lr"],
        meta_opt=mix["meta_opt"], meta_lr=mix["meta_lr"],
        method=mix["method"], unroll_steps=mix["unroll"],
        mesh=make_mesh(chips), schedule=mix["schedule"],
        checkpoint_dir=None, obs=None,
    )
    return cfg, model, learner


def replicated(mesh):
    """The sharding of the learner's state: whole on every chip of the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def batch_shardings(mesh, schedule: str, base, meta):
    """Where a data loader puts each batch: split over the chips on the
    single-sync schedule (the shard_map's in_specs), on the mesh otherwise."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def spec(x, lead):
        if schedule != "single_sync":
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*((None,) * lead + ("data",) + (None,) * (x.ndim - lead - 1))))

    return (jax.tree_util.tree_map(lambda x: spec(x, 1), base),
            jax.tree_util.tree_map(lambda x: spec(x, 0), meta))
