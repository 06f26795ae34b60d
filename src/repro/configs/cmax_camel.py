"""The paper's own pipeline configuration: CMAX-CAMEL on a DAVIS240C
(240x180) with 40,000-event windows, three coarse-to-fine stages
(s = 1/4, 1/2, 1; 3/5/9-tap Gaussians; keep-ratio rho_s = s) and the
runtime-adaptive controller (Alg. 1).

`MEGAKERNEL` is the same deployment on the batched Pallas megakernel. Its
per-(window, slab) tap budget is sized for the synthetic generator's
40,000-event windows (data/events.py): seeds 100-103 put at most 20,154
contributing taps into one 8-row slab at s = 1 (13,856 at s = 1/4,
15,846 at s = 1/2), and seeds 200-231 at most 22,466 (15,130; 17,890),
counted at omega = 0, the truth and 1.3x the truth. 24,576 slots (96
chunks of 256) covers both. A denser window (the worst case is all 4N =
160,000 taps in one slab) is recomputed exactly by the slow path and
counted in repro_serving_spilled_taps_total.
"""
import dataclasses

from repro.core.types import Camera, CmaxConfig, fixed_schedule_config, \
    full_resolution_config

CAMERA = Camera()                       # DAVIS240C
CONFIG = CmaxConfig(camera=CAMERA)      # runtime-adaptive (the paper)
FIXED = fixed_schedule_config(CAMERA)   # fixed-schedule baseline
FULLRES = full_resolution_config(CAMERA)  # conventional full-res CMAX
EVENTS_PER_WINDOW = 40000
ENGINE_CAPACITY = 24576
MEGAKERNEL = dataclasses.replace(CONFIG, engine="pallas_batched",
                                 engine_capacity=ENGINE_CAPACITY)
