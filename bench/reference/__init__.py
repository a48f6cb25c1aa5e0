"""Plain float32 references of what the timed path computes. They
import nothing of the program under ``src/``."""
