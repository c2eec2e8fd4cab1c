"""Uniform field-kernel interface over the two device arithmetic paths.

Every aggregation-round body needs the same eight operations (canonicalize,
add, sub, axis-sum, uniform draws, matrix contraction, u64 reduction,
int64 export) in one of two implementations:

- the **uint32 Solinas fast path** (`fastfield`): canonical residues in
  uint32 lanes, shift/add reduction — for moduli of form 2^b - delta;
- the **generic int64 path** (`modular`): any modulus < 2^31 (matmul) or
  < 2^62 (elementwise), emulated 64-bit lanes on TPU.

``FieldOps.create`` picks the fast path when the modulus qualifies AND the
caller's cross-device sums provably fit uint32 (``cross_terms`` = the
maximum residues summed by a collective before the next canonicalize).
Results are bit-identical between paths (tests/test_fastfield.py); only
speed and dtype differ. The adapter collapses what used to be duplicated
``_local_round``/``_local_round_fast`` bodies in mesh.simpod.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import fastfield, modular


class FieldOps:
    """Field/ring ops mod ``m``; ``sp`` non-None selects the uint32 path.

    Note additive sharing only needs ring structure, so a *composite*
    Solinas-form modulus still rides the fast path — none of these ops
    divide. The packed-Shamir matmuls (which do need a prime) dispatch in
    mesh.simpod's share/reconstruct stages, not here.
    """

    __slots__ = ("m", "sp", "dtype")

    def __init__(self, m: int, sp: Optional[fastfield.SolinasPrime]):
        self.m = int(m)
        self.sp = sp
        self.dtype = jnp.uint32 if sp is not None else jnp.int64

    @classmethod
    def create(cls, modulus: int, *, cross_terms: int = 1) -> "FieldOps":
        sp = fastfield.SolinasPrime.try_from(modulus)
        if sp is not None and cross_terms * (modulus - 1) >= (1 << 32):
            sp = None  # collective partial sums could wrap uint32
        return cls(modulus, sp)

    # -- conversions ------------------------------------------------------
    def to_residues(self, inputs):
        """Any-integer inputs -> canonical residues in the working dtype.
        Every round's residue pass goes through here, so this is where it
        gets its name on the device trace."""
        with jax.named_scope("sda.residues"):
            if self.sp is not None:
                return fastfield.to_residues32(inputs, self.sp)
            return modular.canon(jnp.asarray(inputs, jnp.int64), self.m)

    def to_int64(self, x):
        return x.astype(jnp.int64)

    def from_u64(self, v):
        """uint64 stream draws -> canonical residues (no-reject reduction).
        Over a Solinas modulus the draws' two halves are reduced in uint32
        lanes (``fastfield.reduce64``); any other keeps the 64-bit modulo."""
        if self.sp is not None:
            hi = (v >> jnp.uint64(32)).astype(jnp.uint32)
            return fastfield.reduce64(hi, v.astype(jnp.uint32), self.sp)
        r = jnp.mod(v, jnp.uint64(self.m))
        return r.astype(self.dtype)

    # -- arithmetic -------------------------------------------------------
    def canon(self, x):
        if self.sp is not None:
            return fastfield.canon32(x, self.sp)
        return modular.canon(x, self.m)

    def add(self, a, b):
        if self.sp is not None:
            return fastfield.modadd32(a, b, self.sp)
        return modular.modadd(a, b, self.m)

    def sub(self, a, b):
        if self.sp is not None:
            return fastfield.modsub32(a, b, self.sp)
        return modular.modsub(a, b, self.m)

    def sum(self, x, axis=0):
        if self.sp is not None:
            return fastfield.modsum32(x, self.sp, axis=axis)
        return modular.modsum(x, self.m, axis=axis)

    def uniform(self, key, shape):
        if self.sp is not None:
            return fastfield.uniform32(key, shape, self.sp)
        return modular.uniform_mod(key, tuple(shape), self.m)
