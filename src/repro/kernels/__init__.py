"""Pallas decode kernels and their jnp oracles (the paper's hot spots).

Layout invariants (binding, PR 2 — see docs/ARCHITECTURE.md): every
cache/pool operand is HEAD-MAJOR — contiguous caches [B, Hkv, S, Dh],
layer-stacked page pools [L, P, Hkv, ps, Dh] read at a scalar-prefetched
layer index, Kg [.., Hkv, Dg] — and no kernel (or its ref) may transpose
or materialise a copy of a cache-sized array on the decode path;
page/block-sized temporaries are fine. Int8 pools
add per-(page, head) f32 scale rows threaded as scalar-prefetch operands
with the dequant fused inside the block loop — the fp path with
``k_scales=None`` is byte-for-byte the original program.

Bitwise contracts: ``ref.py`` holds the jnp semantic oracles; each
Pallas kernel must match its ref to float32 accumulation tolerance, and
the fused gate-select kernels reproduce ``sparsity.select_blocks``
exactly (including tie-breaking). Models dispatch through ``ops.py``
(``impl='ref' | 'pallas' | 'pallas_interpret'``) — never import kernel
modules directly.

OPTIONAL layer by repo convention: add <name>.py + ops.py + ref.py only
for compute hot-spots the paper itself optimizes with a custom kernel.
"""
