"""Traffic: how a cell drives its job (``<traffic>.json``: the rounds to
a block, each block followed by the log flush and the fairness eval),
and the generators that make its data and client networks from the
seed.

The generators here are frozen copies of the program's own
(`data/synthetic.generate_synthetic`, `network/trace.sample_networks`),
so a later change to the program cannot move the benchmark's inputs.
"""
