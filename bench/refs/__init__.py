"""Plain references, one per configuration family.  They import nothing
of the program under test and build everything from the seed and the
configuration file."""
