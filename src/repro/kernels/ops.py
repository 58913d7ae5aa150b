"""The jitted entry for calling a registered Pallas kernel from traced code.

``call(name, shape, *args, config=None)`` resolves the block config with
``registry.block_config`` (explicit blocks win, the kernel's tile rule
fills the rest) and runs ``kdef.call`` under one jit.  Resolution happens
outside the trace: the shape and the blocks are static jit arguments, so a
lookup inside the trace would bake the first answer in forever.

Off a TPU (``registry.interpret_default``) the kernels execute in interpret
mode - the kernel body runs step-by-step in Python/XLA so correctness (and
the BlockSpec tiling logic) is fully exercised without Mosaic.  On a real
v5e the same calls lower to Mosaic TPU kernels.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels import registry as kreg


@partial(jax.jit, static_argnums=(0, 1, 2))
def _call(kernel_call, shape: tuple, config: tuple, *args):
    return kernel_call(dict(shape), args, dict(config), kreg.interpret_default())


def call(name: str, shape: dict, *args, config: Optional[dict] = None):
    """Kernel ``name`` on ``args`` (its registry operands, in order), the
    problem described by ``shape`` in the registry's keys."""
    kdef = kreg.get_kernel(name)
    blocks = kreg.block_config(kdef, shape, args[0].dtype, config)
    return _call(kdef.call, tuple(sorted(shape.items())), tuple(sorted(blocks.items())), *args)
