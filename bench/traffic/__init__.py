"""Traffic drivers, one per kind; a cell's file holds the kind's
parameters (lengths, rates, clients)."""
