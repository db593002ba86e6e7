"""Search spaces + admissibility for tunable kernel block parameters.

Every Pallas kernel (and its XLA software twin) exposes a small set of
integer tile knobs — flash attention's (bq, bk) score tile, swiglu's
(bm, bf, bs) output/hidden tiles, the scan kernels' chunk length, the
software paths' chunk sizes.  This module is the single declaration of

  * which knobs each kernel has, per lowering kind (``HW`` = Pallas
    block sizes, ``SW`` = XLA-path chunking), and the candidate values
    the tuner may sweep;
  * the **admissibility predicate**: MXU/sublane alignment, grid
    divisibility, and a VMEM budget — the same constraints the kernels
    assert at call time, checked *before* a config is ever measured so
    the tuner can never persist a config the kernel would reject;
  * for swiglu, the scoped VMEM its tiles ask of Mosaic
    (``_swiglu_hw_vmem``) and the ``vmem_limit_bytes`` a call then passes
    (``vmem_limit``): the kernel wrapper and the admissibility check read
    the same estimate against the same cap.

Shapes are canonical tuples (the same ones ``tuning.lookup`` keys on):

  flash_attention  (B, Sq, Skv, H, Hkv, D)
  swiglu_mlp       (M, D, F)
  mamba2_ssd       (B, S, H, P, N)
  rwkv6_wkv        (B, S, H, K, V)
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Callable, Dict, Mapping, Optional, Sequence, Tuple

# Lowering kinds a space is declared for (mirrors viscosity HW/SW without
# importing it: this module stays a leaf).
HW = "hw"
SW = "sw"

# TPU geometry the admissibility rules encode (see guides/pallas_guide.md):
# MXU is 128x128, the f32 min tile is (8, 128).  Mosaic gives a kernel a
# 16 MiB scoped-VMEM limit unless the call asks for more; flash attention
# budgets half of that for the blocks a single grid step holds live.
MXU_LANE = 128
SUBLANE_F32 = 8
MIB = 1024 * 1024
VMEM_BUDGET_BYTES = 8 * MIB

# Scoped VMEM beyond the default (swiglu).  A v5e TensorCore has 128 MiB
# of VMEM; a call may ask for at most half of it.  Mosaic's own scratch
# (f32 operand casts, gate tiles) comes on top of the blocks the estimate
# counts: 1.1-5.6 MiB at d_model 5120 on a described v5e, so a call asks
# for its blocks plus a fixed headroom.
VMEM_BYTES = 128 * MIB
SCOPED_VMEM_DEFAULT = 16 * MIB
SCOPED_VMEM_HEADROOM = 8 * MIB
SCOPED_VMEM_CAP = VMEM_BYTES // 2


@dataclass(frozen=True)
class KernelSpace:
    """The tunable knobs of one (kernel, lowering-kind) pair.

    ``params`` maps knob name -> ordered candidate values (ascending, so
    the hillclimber's neighbor move is "one index up/down").
    ``admissible(cfg, shape)`` is the hard constraint; ``vmem(cfg, shape)``
    estimates live block bytes, which must not pass ``vmem_budget`` (HW
    spaces only).
    """

    kernel: str
    kind: str
    params: Mapping[str, Tuple[int, ...]]
    check: Optional[Callable[[Dict[str, int], Tuple[int, ...]], bool]] = None
    vmem: Optional[Callable[[Dict[str, int], Tuple[int, ...]], int]] = None
    defaults: Mapping[str, int] = field(default_factory=dict)
    vmem_budget: int = VMEM_BUDGET_BYTES

    def admissible(self, cfg: Mapping[str, int],
                   shape: Tuple[int, ...]) -> bool:
        """Is ``cfg`` one the kernel will accept for ``shape``?"""
        for name, choices in self.params.items():
            if name not in cfg or cfg[name] not in choices:
                return False
        cfg = dict(cfg)
        if self.check is not None and not self.check(cfg, tuple(shape)):
            return False
        if self.vmem is not None and self.vmem(cfg, tuple(shape)) > \
                self.vmem_budget:
            return False
        return True

    def configs(self, shape: Tuple[int, ...]):
        """All admissible configs for ``shape`` (the sweep grid)."""
        names = sorted(self.params)
        for vals in itertools.product(*(self.params[n] for n in names)):
            cfg = dict(zip(names, vals))
            if self.admissible(cfg, shape):
                yield cfg

    def neighbors(self, cfg: Mapping[str, int], shape: Tuple[int, ...]):
        """Admissible one-step moves (one knob, one choice index up/down)
        — the hillclimber's proposal set."""
        for name in sorted(self.params):
            choices = self.params[name]
            i = choices.index(cfg[name])
            for j in (i - 1, i + 1):
                if 0 <= j < len(choices):
                    cand = dict(cfg)
                    cand[name] = choices[j]
                    if self.admissible(cand, shape):
                        yield cand


# ------------------------------------------------------------ flash attn
def _roundup(n: int, m: int) -> int:
    return -(-n // m) * m


def _flash_hw_check(cfg, shape):
    _B, Sq, Skv, _H, _Hkv, _D = shape
    bq, bk = cfg["bq"], cfg["bk"]
    # ops.py pads S up to a block multiple; a block is admissible when it
    # is sublane-aligned and no larger than the padded sequence extent
    # (anything bigger is pure padding work the tuner must not propose).
    return (bq % SUBLANE_F32 == 0 and bk % SUBLANE_F32 == 0
            and bq <= _roundup(max(SUBLANE_F32, Sq), SUBLANE_F32)
            and bk <= _roundup(max(SUBLANE_F32, Skv), SUBLANE_F32))


def _flash_hw_vmem(cfg, shape):
    _B, _Sq, _Skv, _H, _Hkv, D = shape
    bq, bk = cfg["bq"], cfg["bk"]
    # live blocks: q (bq, D), k/v (bk, D), scores (bq, bk), acc (bq, D)
    return 4 * (bq * D + 2 * bk * D + bq * bk + bq * D + 2 * bq)


def _flash_sw_check(cfg, shape):
    _B, _Sq, Skv, _H, _Hkv, _D = shape
    # attention_chunked clamps to min(kv_chunk, Skv) and pads: any positive
    # chunk runs, but chunks beyond Skv are equivalent to Skv.
    return 0 < cfg["kv_chunk"] <= max(128, 2 * Skv)


# ---------------------------------------------------------------- swiglu
def _swiglu_hw_check(cfg, shape):
    M, _D, F = shape
    bm, bf, bs = cfg["bm"], cfg["bf"], cfg["bs"]
    # kernel.py asserts M % bm == 0 and F % bf == 0 (after clamping to the
    # dims) and streams the hidden tile in bs sub-columns: bs | bf.
    bm, bf = min(bm, M), min(bf, F)
    return M % bm == 0 and F % bf == 0 and bf % min(bs, bf) == 0


def _swiglu_hw_vmem(cfg, shape, itemsize: int = 2) -> int:
    """Scoped VMEM (bytes) the swiglu kernel's blocks hold: Mosaic double
    buffers every block (x (bm, D), w1/w3 (D, bf), w2 (bf, D), out (bm,
    D), in the operands' dtype, bf16 when served) and keeps the f32 (bm,
    D) accumulator once.  At d_model 5120, d_ff 14336 it gives what a
    described v5e's compiler reports for the blocks: 22.50M (bm 128, bf
    256), 30.47M (bm 8, bf 512; reported 30.31M), 37.50M (bm 128, bf 512);
    at bm 128, bf 128 it gives 15.00M, and Mosaic's own scratch takes the
    call to 16.13M."""
    M, D, F = shape
    bm, bf = min(cfg["bm"], M), min(cfg["bf"], F)
    return 2 * itemsize * (2 * bm * D + 3 * D * bf) + 4 * bm * D


def vmem_limit(need: int) -> Optional[int]:
    """The ``vmem_limit_bytes`` a call whose blocks hold ``need`` bytes
    passes: None (Mosaic's default) where they fit it with the headroom,
    else the blocks plus the headroom."""
    ask = need + SCOPED_VMEM_HEADROOM
    return None if ask <= SCOPED_VMEM_DEFAULT else ask


# ------------------------------------------------------------ scan chunks
def _chunk_check(cfg, shape):
    S = shape[1]
    return 0 < cfg["chunk"] <= max(16, S)


SPACES: Dict[Tuple[str, str], KernelSpace] = {}


def _declare(space: KernelSpace) -> KernelSpace:
    SPACES[(space.kernel, space.kind)] = space
    return space


_declare(KernelSpace(
    kernel="flash_attention", kind=HW,
    params={"bq": (8, 16, 32, 64, 128, 256),
            "bk": (8, 16, 32, 64, 128, 256, 512)},
    check=_flash_hw_check, vmem=_flash_hw_vmem,
    defaults={"bq": 128, "bk": 128},
))
_declare(KernelSpace(
    kernel="flash_attention", kind=SW,
    params={"kv_chunk": (64, 128, 256, 512, 1024, 2048)},
    check=_flash_sw_check,
    defaults={"kv_chunk": 512},
))
_declare(KernelSpace(
    kernel="swiglu_mlp", kind=HW,
    params={"bm": (8, 16, 32, 64, 128, 256),
            "bf": (128, 256, 512, 1024),
            "bs": (128, 256, 512)},
    check=_swiglu_hw_check, vmem=_swiglu_hw_vmem,
    defaults={"bm": 128, "bf": 512, "bs": 128},
    vmem_budget=SCOPED_VMEM_CAP - SCOPED_VMEM_HEADROOM,
))
_declare(KernelSpace(
    kernel="mamba2_ssd", kind=HW,
    params={"chunk": (16, 32, 64, 128, 256)},
    check=_chunk_check,
    defaults={"chunk": 128},
))
_declare(KernelSpace(
    kernel="mamba2_ssd", kind=SW,
    params={"chunk": (16, 32, 64, 128, 256)},
    check=_chunk_check,
    defaults={"chunk": 128},
))
_declare(KernelSpace(
    kernel="rwkv6_wkv", kind=HW,
    params={"chunk": (8, 16, 32, 64, 128)},
    check=_chunk_check,
    defaults={"chunk": 16},
))
_declare(KernelSpace(
    kernel="rwkv6_wkv", kind=SW,
    params={"chunk": (8, 16, 32, 64, 128)},
    check=_chunk_check,
    defaults={"chunk": 16},
))


def space_for(kernel: str, kind: str) -> Optional[KernelSpace]:
    return SPACES.get((kernel, kind))


def admissible(kernel: str, kind: str, cfg: Mapping[str, int],
               shape: Sequence[int]) -> bool:
    """Module-level predicate (what the property tests call)."""
    space = space_for(kernel, kind)
    return space is not None and space.admissible(cfg, tuple(shape))
