"""Operation and byte counts of the solvers, from shapes alone.  Each is a LOWER
bound on the work, so a roofline share built on it cannot pass 100%."""
