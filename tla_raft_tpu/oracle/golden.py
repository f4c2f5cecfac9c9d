"""Golden state counts of the reference Raft configs.

Reference data for the tests and ``chip_smoke.py``: numbers pinned
from independent engines, so a change to the checker is held to counts
it did not produce.  ``benchmark/reference/answers/`` holds the same
per-level counts for the benchmark's configs, and
``tests/test_golden.py`` ties the two together.
"""

# Full-space golden totals for completed (empty-frontier) fixpoint runs,
# keyed (S, V, max_election, max_restart) -> (distinct, generated, depth).
# Pinned from the independent native C++ checker (native/cpubase.cpp)
# and confirmed by the python oracle.  The as-is reference config's
# fixpoint (~10^9 states, BASELINE.md) has not been reached by any
# engine yet and stays unpinned.
GOLDEN_FULL = {
    (3, 1, 2, 1): (180_582, 747_500, 35),  # cpubase ≡ oracle (exact)
    (3, 1, 2, 2): (223_437, 936_729, 36),  # cpubase ≡ oracle (exact)
    # cpubase ≡ oracle (exact, round 5: 2.9-h oracle fixpoint run,
    # docs/ORACLE_FIX_V2ME2MR0.json — config [3,2,2,0], identical
    # distinct/generated/depth)
    (3, 2, 2, 0): (4_850_261, 26_087_894, 45),
}

# Per-level new-state counts of the deepest verified record (BASELINE.md
# "golden counts": levels 0-15 double-verified oracle+engine, 16+ device-
# produced with disjoint-new delta audits).  Any run deep enough to
# overlap this prefix is gated on it level for level — the numbers the
# project leans on hardest must be regression-checked, not prose-only.
GOLDEN_LEVELS = {
    (3, 2, 3, 3): [
        1, 1, 3, 9, 22, 57, 136, 345, 931, 2468, 5881, 12505, 24705,
        47599, 91014, 169607, 301664, 511609, 839797, 1353766, 2150466,
        3350017, 5099018, 7596394, 11125029, 16077143, 22959572,
        32391457, 45102507,
    ],
}
