"""System modules: each builds the system under test for a configuration
file that names it (``"system"``), with weights from ``bench.weights``."""
