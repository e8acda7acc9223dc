"""One module per configuration kind (``runner`` in the configuration's
file).  A runner builds the system under test from the seed, warms it,
drives the window, and compares what the window produced with the plain
references under ``bench/reference``."""
