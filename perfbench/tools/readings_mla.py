"""Readings that the limits of an ``lm_mla_train`` cell's ``correct`` are set
from (PERF.md): ``readings_moe.py``'s (the program against the plain
reference for each seed, the bfloat16 control, a state left unchanged) with
this reference's own faults: the MTP term left out, the key-value latent
without its norm, the rope turning halves instead of adjacent pairs. Run on
the chip at the cell's own size:

    python3 perfbench/tools/readings_mla.py --workload W --seeds 1,2 \
        [--control] [--faults]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import readings_moe  # noqa: E402

readings_moe.FAULTS = ("no_mtp", "no_kv_norm", "half_rope")

if __name__ == "__main__":
    readings_moe.main()
