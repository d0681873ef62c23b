"""Plain references the benchmark judges the program by.  They import
nothing of the program under test."""
