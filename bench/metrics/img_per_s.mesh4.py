"""img_per_s.mesh4: ``img_per_s`` in the 4-chip cells, whose host holds
all its cores and whose runs spread far less than a one-chip cell's, so
that it carries a bound of its own."""
import spec

read = spec.reader("img_per_s")
