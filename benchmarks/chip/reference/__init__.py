"""The plain reference the benchmark decides ``correct`` by: an Euler
circuit checker and a sequential Hierholzer, independent of ``repro``."""
