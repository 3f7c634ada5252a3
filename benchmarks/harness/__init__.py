"""The yardstick: what every driver and reader shares.  Nothing here
imports the program's model code; the program enters through the drivers."""
