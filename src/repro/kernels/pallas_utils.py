"""Tiny shared helpers for the Pallas TPU kernels (fused-CE, top-k,
token scoring, paged attention): the common compiler parameters, the
interpret-mode backend check, and the kernels a compiled program holds."""

from __future__ import annotations

import re
from typing import List, Optional, Tuple

import jax
from jax.experimental.pallas import tpu as pltpu

from repro.core.windows import scoped_vmem_limit


def compiler_params(working_set_bytes: Optional[int] = None,
                    semantics: Tuple[str, ...] = ("parallel", "arbitrary")):
    """dimension_semantics: by default the first grid axis parallel, the
    second sequential — the layout the kernels of this repo use (state is
    carried across the innermost, sequential axis).  A kernel that carries
    state across both axes (the fused loss's backward) passes
    ("arbitrary", "arbitrary").

    `working_set_bytes` (the kernel's VMEM model for one grid step, from
    `core/windows.py`) sets Mosaic's scoped-VMEM limit to cover it;
    without it the compiler's default limit applies."""
    kw = {}
    if working_set_bytes is not None:
        kw["vmem_limit_bytes"] = scoped_vmem_limit(working_set_bytes)
    return pltpu.CompilerParams(dimension_semantics=semantics, **kw)


def interpret_default() -> bool:
    """Interpret mode everywhere but real TPU."""
    return jax.default_backend() != "tpu"


# a compiled kernel is a tpu_custom_call instruction named after the
# kernel's `pallas_call(name=...)`, with a ".N" uniquifier
_KERNEL_CALL_RE = re.compile(
    r"%([A-Za-z_][\w\-]*?)(?:\.\d+)* = [^\n]*"
    r'custom_call_target="tpu_custom_call"')


def tpu_kernels(hlo_text: str) -> List[str]:
    """Names of the Pallas kernel calls in a TPU program's HLO text
    (``compiled.as_text()``), one entry per call."""
    return _KERNEL_CALL_RE.findall(hlo_text)
