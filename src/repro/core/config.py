"""Configuration for the Dynamic Prober (paper §4).

All sizes that shape arrays are static Python ints so everything jits with
fixed shapes. ``a = ln(1/delta)`` is the Chernoff confidence constant from
paper §4.5 (their running example uses delta = 1e-3, i.e. a = ln(1000)).
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class ProberConfig:
    # --- LSH index (paper §2.2, §4.2) ---
    n_tables: int = 2          # L hash tables
    n_funcs: int = 10          # K hash functions per table
    n_regions: int = 4         # target distinct values per function (Ex. 4.1)
    # --- adaptive probing (paper §4.3/4.4, Alg. 1) ---
    max_visit: int = 8192      # maxVisit: total candidate budget across rings
    ring_budget: int = 4096    # R_max: max candidates gathered per ring
    central_budget: int = 4096 # cap for the exact central-bucket pass (Alg. 3)
    # --- progressive sampling (paper §4.5, Alg. 2) ---
    s1: float = 0.05           # initial sampling rate
    s_max: float = 1.0         # maximum sampling rate
    eps: float = 0.01          # error-bound parameter epsilon
    delta: float = 1e-3        # failure probability (a = ln(1/delta))
    chunk: int = 256           # candidates evaluated per while_loop iteration
    schedule_checks: bool = True   # bound checks only at s_{i+1}=2 s_i points
    # --- PQ / ADC (paper §4.6, Alg. 4/5) ---
    use_pq: bool = False
    pq_m: int = 8              # M subspaces
    pq_kc: int = 16            # Kc centroids per subspace
    pq_iters: int = 8          # Lloyd iterations at build
    pq_int8_lut: bool = False  # quantized ADC datapath (DESIGN.md §11):
                               # per-query affine uint8 LUT + int32 accumulate,
                               # threshold compared in the quantized domain.
                               # Qualification matches float32 ADC exactly
                               # outside a ±(M/2+1)·scale band around tau^2.
                               # Ignored when pq_banded (band needs floats).
    pq_pack4: bool = False     # pack two 4-bit PQ codes per byte (requires
                               # Kc <= 16 and even M) — halves code-matrix
                               # bandwidth in the hot loop (DESIGN.md §11)
    pq_banded: bool = False    # residual-banded ADC qualification — measured
                               # WORSE than the hard threshold once near rings
                               # are exact (see EXPERIMENTS.md §Perf); kept as
                               # an option. False = paper-faithful hard test.
    pq_exact_rings: int = 2    # beyond-paper: rings k <= this use exact L2
                               # (near rings carry the selectivity mass —
                               # paper Fig. 1); 0 = ADC everywhere (faithful)
    pq_exact_central: bool = True  # Alg. 3 brute-forces B_central with exact
                               # L2 (paper-faithful). False = ADC there too:
                               # the whole estimate then runs off the byte
                               # codes, never touching the float corpus — the
                               # high-throughput serving trade (DESIGN.md §9)
    # --- skew-resilient probe scheduling (DESIGN.md §11) ---
    lane_block: int = 4        # slab iterations run between lane compactions
                               # of the batched prober; 0 = monolithic
                               # while_loop (no compaction). Results are
                               # bit-identical for every value.
    lane_tile: int = 16        # lanes processed per compacted tile — work
                               # granularity after compaction (static shape).
                               # Batches with Q·L <= lane_tile lanes stay on
                               # the monolithic loop (one tile can't retire
                               # work early, so compacting it is overhead).
                               # Tiles run SEQUENTIALLY, so size this toward
                               # the backend's parallel width: 16 suits the
                               # CPU host measured in DESIGN.md §11; on a
                               # wide-parallel backend (GPU/TPU) raise it
                               # (or set lane_block=0) so compaction never
                               # trades free lane parallelism for depth
    # --- neighbor lookup (paper §4.7, Alg. 6) ---
    table_max_dist: int = 6    # M: distances above this are not stored
    # --- dynamic updates / serving ingest (paper §5, DESIGN.md §10) ---
    ingest_chunk: int = 256    # serve-layer ingest batch: pending points are
                               # applied in fixed chunks of this size so the
                               # jitted in-capacity update step never sees a
                               # new shape (power of two recommended)
    # --- kernels ---
    use_kernels: bool = False  # route hot loops through the Pallas kernels
                               # (compiled on TPU; interpreted on the CPU —
                               # correct but slow, so off by default)

    @property
    def a_const(self) -> float:
        return math.log(1.0 / self.delta)

    def replace(self, **kw) -> "ProberConfig":
        return dataclasses.replace(self, **kw)
