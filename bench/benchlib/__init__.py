"""The chip benchmark's yardstick: traffic, open loop, reference
comparison and trace reduction.  Nothing here imports the program."""
