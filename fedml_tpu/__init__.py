"""fedml_tpu — a TPU-native federated-learning + distributed-training framework.

Top-level API parity with the reference (``python/fedml/__init__.py``):
``init()``, ``run_simulation()``, ``run_cross_silo_server()/client()``,
``run_hierarchical_cross_silo_server()/client()`` — re-designed for JAX/XLA:
simulation compiles whole FL rounds to single XLA programs over a device mesh;
cross-silo keeps a message-driven plane only where real network boundaries
exist.
"""

from __future__ import annotations

__version__ = "0.1.0"

from typing import Any, Dict, Optional

from . import constants
from .arguments import Arguments, load_arguments
from .constants import (
    FEDML_SIMULATION_TYPE_MPI,
    FEDML_SIMULATION_TYPE_NCCL,
    FEDML_SIMULATION_TYPE_SP,
    FEDML_SIMULATION_TYPE_TPU,
    FEDML_TRAINING_PLATFORM_CROSS_DEVICE,
    FEDML_TRAINING_PLATFORM_CROSS_SILO,
    FEDML_TRAINING_PLATFORM_SIMULATION,
)
from .utils import set_seeds

_global_args: Optional[Arguments] = None


def init(args: Optional[Arguments] = None, config: Optional[Dict[str, Any]] = None) -> Arguments:
    """Global init (reference ``fedml.init()``, __init__.py:27): place the
    compile cache, load args, seed, initialize multi-host JAX if env says
    so."""
    global _global_args
    from .utils.compile_cache import configure_compile_cache

    configure_compile_cache()  # must precede the first compile
    if args is None:
        args = load_arguments(override=config)
    set_seeds(int(getattr(args, "random_seed", 0)))
    from .core import telemetry

    telemetry.configure_from_args(args)
    from .parallel.mesh import maybe_initialize_distributed

    maybe_initialize_distributed(args)
    _global_args = args
    return args


def run_simulation(backend: str = FEDML_SIMULATION_TYPE_SP, args: Optional[Arguments] = None):
    """Reference ``fedml.run_simulation()`` (launch_simulation.py:10)."""
    from .simulation import SimulatorSingleProcess, SimulatorTPU

    args = args or _global_args or init()
    backend = getattr(args, "backend", None) or backend
    if backend == FEDML_SIMULATION_TYPE_SP:
        simulator = SimulatorSingleProcess(args)
    elif backend in (
        FEDML_SIMULATION_TYPE_TPU,
        FEDML_SIMULATION_TYPE_NCCL,
        FEDML_SIMULATION_TYPE_MPI,
    ):
        simulator = SimulatorTPU(args)
    else:
        raise ValueError(f"unknown simulation backend '{backend}'")
    return simulator.run()


def run_cross_silo_server(args: Optional[Arguments] = None):
    """Reference ``fedml.run_cross_silo_server()`` (launch_cross_silo_horizontal.py:6)."""
    from .cross_silo import Server

    args = args or _global_args or init()
    return Server(args).run()


def run_cross_silo_client(args: Optional[Arguments] = None):
    from .cross_silo import Client

    args = args or _global_args or init()
    return Client(args).run()


def run_mnn_server(args: Optional[Arguments] = None):
    """Reference ``fedml.run_mnn_server()`` (launch_cross_device.py:6)."""
    import jax as _jax

    from . import data as _data, models as _models
    from .cross_device import ServerMNN

    args = args or _global_args or init()
    fed_data, output_dim = _data.load(args)
    model = _models.create(args, output_dim)
    sample = _models.sample_input_for(args, fed_data)
    variables = _models.init_params(
        model, _jax.random.PRNGKey(int(getattr(args, "random_seed", 0))), sample
    )

    def apply_fn(vars_, x, train=False, rngs=None):
        return model.apply(vars_, x, train=train, rngs=rngs)

    return ServerMNN(
        args, fed_data, variables, apply_fn=apply_fn,
        backend=str(getattr(args, "backend", "LOOPBACK")),
    ).run()


def run_hierarchical_cross_silo_server(args: Optional[Arguments] = None):
    from .cross_silo import HierarchicalServer

    args = args or _global_args or init()
    return HierarchicalServer(args).run()


def run_hierarchical_cross_silo_client(args: Optional[Arguments] = None):
    from .cross_silo import HierarchicalClient

    args = args or _global_args or init()
    return HierarchicalClient(args).run()


def run_centralized(args: Optional[Arguments] = None):
    """Centralized (non-federated) baseline over the same data plane —
    reference ``centralized/centralized_trainer.py:9``."""
    from .centralized import run_centralized as _run

    args = args or _global_args or init()
    return _run(args)
