"""host_ms_per_step.mesh4: ``host_ms_per_step`` in the 4-chip cells,
where it moves ``img_per_s.mesh4``."""
import spec

read = spec.reader("host_ms_per_step")
