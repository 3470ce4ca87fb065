"""One driver per kind of entry point. A configuration names its driver;
``run(cell, seed=, seconds=, window=, devices=, t_start=)`` sets the cell
up, measures it inside ``window``, compares what the window produced with
the plain reference, and returns the run's record."""
