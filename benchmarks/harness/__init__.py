"""The yardstick: the window clock, the trace reduction, the table of peaks
and the check of the result line. Nothing here imports the program."""
