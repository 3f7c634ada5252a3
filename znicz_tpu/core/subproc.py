"""Process-level task parallelism for searches and ensembles.

Capability parity with the reference's genuinely-parallel modes [SURVEY.md
2.5: ``veles/genetics/`` and ``veles/ensemble/`` ran many workflow instances
concurrently at process level].  Each worker process loads the workflow
module fresh (the reference ``run(load, main)`` two-file convention), seeds
the PRNG registry from its payload, trains, and returns a small result —
full isolation, so results are deterministic given seeds and independent of
worker count or completion order.

Workers inherit the parent's environment, and with it the platform; a
``device`` in the payload overrides it.  A jax process holds every local
chip it opens, so a pool on the accelerator is one worker wide and its
parent stays off jax until it is done (``backend.check_workers`` refuses
anything else before a child starts); on CPU (``--device cpu``) each
worker is a true extra core-set.
"""

from __future__ import annotations

import os
import pickle
import sys
from typing import Any, Dict, List, Optional, Sequence


def _run_workflow_module(
    workflow_path: str,
    config_path: Optional[str],
    *,
    seed: Optional[int],
    stop_after: Optional[int],
    device: Optional[str] = None,
    genome: Optional[Sequence[float]] = None,
    dry_run: bool = False,
    export: Optional[str] = None,
):
    """Load + run a workflow module the way the launcher does; returns
    (launcher, decision).  ``genome`` (optional) is applied to the config
    tree's Tune leaves after the module loads, before it runs."""
    from znicz_tpu.core import backend
    from znicz_tpu.core.config import root
    from znicz_tpu.launcher import Launcher, _load_module, make_parser

    argv = [workflow_path] + ([config_path] if config_path else [])
    args = make_parser().parse_args(argv)
    args.random_seed = seed
    args.stop_after = stop_after
    args.dry_run = dry_run
    args.export = export
    backend.require(device)
    backend.enable_compile_cache()
    launcher = Launcher(args)
    sys.path.insert(0, os.path.dirname(os.path.abspath(workflow_path)))
    module = _load_module(workflow_path, "__znicz_workflow__")
    if config_path:
        _load_module(config_path, "__znicz_config__")
    if genome is not None:
        from znicz_tpu.genetics import find_tunables

        tunables = find_tunables(root)
        if len(tunables) != len(genome):
            raise ValueError(
                f"worker found {len(tunables)} Tune leaves but the genome "
                f"has {len(genome)} genes; the workflow module must "
                "register its tunables at import time"
            )
        for v, (node, key, _) in zip(genome, tunables):
            node[key] = v
    box: Dict[str, Any] = {}

    def load(cls, *a, **kw):
        return launcher.load(cls, *a, **kw)

    def main(**kw):
        box["decision"] = launcher.main(**kw)

    module.run(load, main)
    return launcher, box.get("decision")


def eval_genome(payload: Dict[str, Any]) -> float:
    """Worker: one genetic-search evaluation; returns fitness (lower is
    better).  Payload keys: workflow, config, seed, stop_after, device,
    genome."""
    _, dec = _run_workflow_module(
        payload["workflow"],
        payload.get("config"),
        seed=payload.get("seed"),
        stop_after=payload.get("stop_after"),
        device=payload.get("device"),
        genome=payload["genome"],
    )
    if dec is None or dec.best_value is None:
        return float("inf")
    return float(dec.best_value)


def train_member(payload: Dict[str, Any]) -> Dict[str, Any]:
    """Worker: train one ensemble member; pickles the trained params to
    ``payload['params_path']`` and returns {'best_value', 'params_path'}."""
    import jax

    launcher, dec = _run_workflow_module(
        payload["workflow"],
        payload.get("config"),
        seed=payload.get("seed"),
        stop_after=payload.get("stop_after"),
        device=payload.get("device"),
    )
    params = jax.device_get(launcher.workflow.state.params)
    with open(payload["params_path"], "wb") as f:
        pickle.dump(params, f)
    return {
        "best_value": None if dec is None else dec.best_value,
        "params_path": payload["params_path"],
    }


def probe_export(payload: Dict[str, Any]) -> None:
    """Worker: the launcher's ``--export`` precheck — build the workflow
    (a dry run trains nothing) and let an unexportable model raise."""
    _run_workflow_module(
        payload["workflow"],
        payload.get("config"),
        seed=payload.get("seed"),
        stop_after=payload.get("stop_after"),
        device=payload.get("device"),
        dry_run=True,
        export=payload["export"],
    )


def run_pool(fn, payloads: List[Dict[str, Any]], n_workers: int) -> list:
    """Map ``fn`` over payloads with n_workers spawned processes (order
    preserved).  n_workers<=1 still uses ONE worker process so results are
    identical to the concurrent path (fresh interpreter per evaluation
    semantics differ from in-process evaluation)."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    ctx = multiprocessing.get_context("spawn")
    # max_tasks_per_child=1: a FRESH interpreter per evaluation, so no
    # config-tree or PRNG state leaks between evaluations sharing a worker
    with ProcessPoolExecutor(
        max_workers=max(1, n_workers), mp_context=ctx, max_tasks_per_child=1
    ) as ex:
        return list(ex.map(fn, payloads))
