"""The benchmark's own code: traffic, corpus, reference, comparison, trace
reduction and the table of peaks. Only ``system.py`` imports the program."""
