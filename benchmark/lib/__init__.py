"""The benchmark's own code: nothing here imports the program under test."""
