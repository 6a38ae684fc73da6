"""What the benchmark knows of Kimi-K2.7-Code's language model: the mapping
to the program's settings, the seeded weights, the plain reference and the
cost functions. A configuration file names this package under ``"modules"``."""
