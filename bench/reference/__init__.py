"""Plain references of the configurations the benchmark runs. They
import nothing of the program."""
