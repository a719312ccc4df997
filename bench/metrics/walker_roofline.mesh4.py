"""walker_roofline.mesh4: ``walker_roofline`` in the 4-chip cells,
where it moves ``img_per_s.mesh4``."""
import spec

read = spec.reader("walker_roofline")
