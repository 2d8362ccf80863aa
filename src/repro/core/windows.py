"""Window / block-size selection (paper §3.2.1, adapted to TPU VMEM).

The paper exposes a tunable "window size" W that splits the vocabulary loop
into chunks so small-(B*T) problems still saturate the GPU.  On TPU the
analogous knobs are the Pallas BlockSpec tile shapes:

  block_rows — rows of H per grid step         (bm)
  block_v    — vocab columns per grid step     (bv)

The VMEM working set of one forward grid step is

  bm*d (H tile, bf16/f32) + bv*d (W tile) + bm*bv (logits tile, f32)
  + O(bm) state

and must fit the ~16 MiB/core VMEM of TPU v5e with headroom for double
buffering.  MXU efficiency wants every matmul dim to be a multiple of 128
(lanes) and the sublane dim a multiple of 8.  `choose_blocks` encodes that
napkin math so callers never hand-tune (DESIGN.md §3.1).

`choose_blocks` is the one owner of the vocabulary kernels' tile plans
(fused CE, top-k, token scoring): a pure function of the problem's shape
and itemsize, decided at trace time and never measured or cached
(DESIGN.md §3.2).
"""

from __future__ import annotations

import dataclasses

# v5e: Mosaic's default scoped-VMEM limit is 16 MiB of the core's 128 MiB;
# plans keep ~45% headroom under the default for double buffering +
# spills (Pallas pipelines input windows, so ~2x the W tile is resident).
VMEM_BYTES = 16 * 1024 * 1024
VMEM_PHYSICAL_BYTES = 128 * 1024 * 1024
_DEFAULT_BUDGET = int(VMEM_BYTES * 0.55)

_LANE = 128
_SUBLANE = 8


def _round_down(x: int, m: int) -> int:
    return max((x // m) * m, m)


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    block_rows: int
    block_v: int
    vmem_bytes: int

    @property
    def shape(self):
        return (self.block_rows, self.block_v)


def tile_bytes(bm: int, bv: int, d: int, in_bytes: int = 2) -> int:
    """Forward-pass VMEM bytes of one grid step (double-buffered inputs)."""
    h_tile = bm * d * in_bytes
    w_tile = bv * d * in_bytes
    logits = bm * bv * 4
    state = 4 * bm * 4  # m, a, z_sum, z_tgt in f32
    return 2 * (h_tile + w_tile) + logits + state


def bwd_tile_bytes(bm: int, bv: int, d: int, in_bytes: int = 2,
                   rmw_dw: bool = True) -> int:
    """VMEM bytes of one grid step of the backward kernel: double-buffered
    H/W tiles and four (bm, 1) f32 row vectors (lane-padded to 128); the
    resident f32 gradient block, double-buffered as an output; the three
    f32 slots of the read-modify-written block (dW's (bv, d) when
    `rmw_dw`, else dH's (bm, d)); and the step's temporaries: the f32
    logit, probability and gradient tiles, the gradient cast to the input
    dtype, and the f32 (bm, d) and (bv, d) products."""
    h_tile = bm * d * in_bytes
    w_tile = bv * d * in_bytes
    rows = 4 * bm * _LANE * 4
    dh, dw = bm * d * 4, bv * d * 4
    resident, rmw = (dh, dw) if rmw_dw else (dw, dh)
    temps = 4 * bm * bv * 4 + (bm + bv) * d * 4
    return 2 * (h_tile + w_tile + rows + resident) + 3 * rmw + temps


def scoped_vmem_limit(working_set_bytes: int) -> int:
    """Mosaic scoped-VMEM limit for a kernel whose VMEM model is
    `working_set_bytes`: twice the model, for what it leaves out (spills,
    relayouts, the bf16 parts an f32-precision dot splits its operands
    into), never below twice the compiler's 16 MiB default and never
    above the v5e's physical VMEM less a reserve."""
    want = max(2 * working_set_bytes, 2 * VMEM_BYTES)
    return int(min(want, VMEM_PHYSICAL_BYTES - VMEM_BYTES))


def choose_blocks(
    n_rows: int,
    vocab: int,
    d: int,
    *,
    in_bytes: int = 2,
    vmem_budget: int = _DEFAULT_BUDGET,
    max_block_rows: int = 1024,
    max_block_v: int = 4096,
) -> BlockPlan:
    """Pick (block_rows, block_v) fitting the VMEM budget.

    Strategy (mirrors the paper's occupancy reasoning):
      * prefer rows tiles of 128-512 — enough MXU work per step;
      * spend the remaining budget on the vocab tile: a larger bv amortizes
        the H-tile fetch across more columns (arithmetic intensity of the
        tile GEMM is ~ 1/(1/bm + 1/bv) MACs/byte);
      * when n_rows is tiny (decode: B*T == B), shrink bm to the real row
        count and grow bv — the TPU analogue of the paper's window strategy
        for small B*T.
    """
    bm = min(_round_down(min(n_rows, 512), _SUBLANE), max_block_rows)
    if n_rows < _SUBLANE:
        bm = _SUBLANE  # pallas pads; rows beyond n are masked by the caller
    bv = max_block_v
    while bv > _LANE and tile_bytes(bm, bv, d, in_bytes) > vmem_budget:
        bv //= 2
    while bm > _SUBLANE and tile_bytes(bm, bv, d, in_bytes) > vmem_budget:
        bm //= 2
    bv = max(_round_down(min(bv, vocab), _LANE), _LANE)
    return BlockPlan(bm, bv, tile_bytes(bm, bv, d, in_bytes))
