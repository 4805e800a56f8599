"""Traffic drivers: ``chipbench/traffic/<mix>.json`` names one of these
modules under ``driver`` and holds its parameters."""
