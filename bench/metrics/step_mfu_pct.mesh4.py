"""step_mfu_pct.mesh4: ``step_mfu_pct`` in the 4-chip cells,
where it moves ``img_per_s.mesh4``."""
import spec

read = spec.reader("step_mfu_pct")
