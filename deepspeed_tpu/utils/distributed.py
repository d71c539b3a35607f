"""Multi-host initialization — the NCCL-rendezvous replacement.

Reference: deepspeed/utils/distributed.py:12-108 (env-var rendezvous +
mpi4py auto-discovery).  TPU-native: ``jax.distributed.initialize`` with a
coordinator address; per-host ONE process owns all local chips (no
CUDA_VISIBLE_DEVICES analog).  Env contract kept as close as possible:

  RANK / WORLD_SIZE            -> process index / process count
  MASTER_ADDR / MASTER_PORT    -> coordinator address
"""
import os

from deepspeed_tpu.utils.logging import logger

_initialized = False


def init_distributed(dist_backend=None, auto_mpi_discovery=True,
                     distributed_port=29500, verbose=True):
    """Join the multi-host world if env vars are present; no-op otherwise.

    dist_backend accepted for API parity (the backend is always XLA
    collectives over ICI/DCN on TPU).
    """
    global _initialized
    if _initialized:
        return
    import jax

    required = ["MASTER_ADDR", "RANK", "WORLD_SIZE"]
    if all(v in os.environ for v in required):
        coordinator = f"{os.environ['MASTER_ADDR']}:" \
                      f"{os.environ.get('MASTER_PORT', distributed_port)}"
        rank = int(os.environ["RANK"])
        world = int(os.environ["WORLD_SIZE"])
        if world > 1:
            if verbose:
                logger.info(
                    f"Initializing jax.distributed: coordinator={coordinator} "
                    f"process={rank}/{world}")
            jax.distributed.initialize(coordinator_address=coordinator,
                                       num_processes=world, process_id=rank)
    elif auto_mpi_discovery and in_mpi_environment():
        rank, world, addr = mpi_discovery()
        if world > 1:
            coordinator = f"{addr}:{distributed_port}"
            if verbose:
                logger.info(f"MPI discovery: coordinator={coordinator} "
                            f"process={rank}/{world}")
            jax.distributed.initialize(coordinator_address=coordinator,
                                       num_processes=world, process_id=rank)
    else:
        if verbose:
            logger.info("Single-process run; skipping jax.distributed init")
    _initialized = True


def in_mpi_environment() -> bool:
    return any(v in os.environ for v in
               ["OMPI_COMM_WORLD_RANK", "PMI_RANK", "SLURM_PROCID"])


def mpi_discovery():
    """Discover (rank, world, master_addr) from MPI/SLURM env (reference
    mpi_discovery, distributed.py:54-96, without requiring mpi4py)."""
    if "OMPI_COMM_WORLD_RANK" in os.environ:
        rank = int(os.environ["OMPI_COMM_WORLD_RANK"])
        world = int(os.environ["OMPI_COMM_WORLD_SIZE"])
    elif "PMI_RANK" in os.environ:
        rank = int(os.environ["PMI_RANK"])
        world = int(os.environ["PMI_SIZE"])
    else:
        rank = int(os.environ["SLURM_PROCID"])
        world = int(os.environ["SLURM_NTASKS"])
    addr = os.environ.get("MASTER_ADDR")
    if addr is None:
        try:
            from mpi4py import MPI

            comm = MPI.COMM_WORLD
            import socket

            addr = comm.bcast(socket.gethostbyname(socket.gethostname()), root=0)
        except ImportError:
            addr = "127.0.0.1"
    os.environ.setdefault("RANK", str(rank))
    os.environ.setdefault("WORLD_SIZE", str(world))
    os.environ.setdefault("MASTER_ADDR", addr)
    return rank, world, addr


def get_rank() -> int:
    import jax

    return jax.process_index()


def get_world_size() -> int:
    import jax

    return jax.process_count()
