"""The benchmark's own yardstick: manifest, estimators, spans, trace
reduction, peaks.  Nothing here imports the program under test."""
