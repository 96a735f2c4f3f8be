"""The chip benchmark's yardstick: everything that turns a cell's name into
runs and numbers.  Nothing here is imported by the system under test."""
