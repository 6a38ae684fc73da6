"""What the benchmark knows of Mellum2-12B-A2.5B's decoder: the mapping to the
program's settings, the seeded weights, the plain reference and the cost
functions. A configuration file names this package under ``"modules"``."""
