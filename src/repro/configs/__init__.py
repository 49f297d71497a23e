"""Architecture registry: importing this package registers every assigned
architecture (plus the paper's own DVB-S2 task chain in dvbs2.py)."""
from repro.configs import (  # noqa: F401
    arctic_480b,
    gemma3_12b,
    gemma3_1b,
    granite_4_0_h_micro,
    internvl2_26b,
    kimi_k2_1t,
    mamba2_1_3b,
    phi3_medium_14b,
    stablelm_3b,
    whisper_small,
    zamba2_7b,
)
from repro.configs import dvbs2  # noqa: F401
