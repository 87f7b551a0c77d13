"""Plain references of the configurations the benchmark runs, each
named by a configuration's ``reference`` key and built by its model
kind's module (``bench/models``). They import nothing of the program."""
