"""fit.retraces_per_job under the name logreg-d3000-iter200's cells report it as."""
from chipbench.harness import load_reader

read = load_reader("fit.retraces_per_job").read
