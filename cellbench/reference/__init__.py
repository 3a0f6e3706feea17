"""Plain references, one per architecture the benchmark runs.  They
import nothing of the program."""
