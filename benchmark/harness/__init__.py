"""The benchmark's own code: nothing here is imported by the program, and
nothing here imports the program except `stacks/` (which builds the system
under test) and the two drivers that call it."""
