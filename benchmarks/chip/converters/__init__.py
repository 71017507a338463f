"""Netlist converters, one module per ``netlist.kind`` of a configuration."""
