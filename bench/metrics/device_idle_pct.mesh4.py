"""device_idle_pct.mesh4: ``device_idle_pct`` in the 4-chip cells,
where it moves ``img_per_s.mesh4``."""
import spec

read = spec.reader("device_idle_pct")
