"""Analytic operation and byte counts of the PFP operators: the work the
algorithm needs, whatever implements it (never read from a compiled
program's cost analysis)."""
