"""Pallas TPU kernels for the single-stage encoder hot path.

histogram.py — 256-bin VMEM histogram (PMF observation / ledger probe)
encode.py    — codebook LUT as one-hot × MXU matmul (the single stage)
bitpack.py   — block-local lookup and bit-packing (one-hot LUT matmul,
               matmul prefix sum + bitfield placement);
               ops.merge_block_streams stitches the blocks
decode.py    — chunked canonical / multi-symbol / QLC decode on the
               scalar core (the receive side of the streaming wire format)
decode_matmul.py — fused coded-weight decode + matmul
platform.py  — interpret on the CPU, Mosaic on a TPU, per call
ops.py       — jit'd public wrappers
ref.py       — pure-jnp oracles used by the bit-exact test sweeps
"""
from . import ops, ref
from .bitpack import lookup_pack_pallas
from .decode import decode_chunks_pallas, decode_chunks_qlc_pallas
from .encode import encode_lookup_pallas
from .histogram import histogram256_pallas
