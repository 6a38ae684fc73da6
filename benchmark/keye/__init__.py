"""What the benchmark knows of Keye-VL-2.0's language model: the mapping to the
program's settings, the seeded weights, the plain reference and the cost
functions. A configuration file names this package under ``"modules"``."""
