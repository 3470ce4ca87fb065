"""Frozen plain references that decide a run's ``correct``.

Nothing here imports the program: ``mapreduce`` restates the five PUMA
jobs in numpy.
"""
