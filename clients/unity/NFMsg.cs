// GENERATED client SDK - do not edit by hand.
// Regenerate with: python -m noahgameframe_tpu.tools.emit_cs_sdk > NFMsg.cs
using System;
using System.Collections.Generic;
using System.IO;
using System.Text;

namespace NFMsg
{
    // ------------------------------------------------------- wire codec
    public static class Nf
    {
        public static readonly byte[] Empty = new byte[0];
        public static byte[] Utf8(string s) { return Encoding.UTF8.GetBytes(s); }
        public static string Str(byte[] b) { return Encoding.UTF8.GetString(b); }

        public static void PutVarint(MemoryStream o, ulong v)
        {
            while (v >= 0x80) { o.WriteByte((byte)((v & 0x7F) | 0x80)); v >>= 7; }
            o.WriteByte((byte)v);
        }
        public static void PutTag(MemoryStream o, uint tag, uint wt)
        {
            PutVarint(o, ((ulong)tag << 3) | wt);
        }
        public static void PutI64(MemoryStream o, long v) { PutVarint(o, (ulong)v); }
        public static void PutF32(MemoryStream o, float v)
        {
            var b = BitConverter.GetBytes(v);
            if (!BitConverter.IsLittleEndian) Array.Reverse(b);
            o.Write(b, 0, 4);
        }
        public static void PutF64(MemoryStream o, double v)
        {
            var b = BitConverter.GetBytes(v);
            if (!BitConverter.IsLittleEndian) Array.Reverse(b);
            o.Write(b, 0, 8);
        }
        public static void PutBytes(MemoryStream o, byte[] v)
        {
            PutVarint(o, (ulong)v.Length); o.Write(v, 0, v.Length);
        }

        // ---------------------------------------------------- 6-byte framing
        // u16 msg-id + u32 total-size, big-endian (total includes header).
        public const uint MaxFrameSize = 64u * 1024u * 1024u;

        public static byte[] Frame(ushort msgId, byte[] body)
        {
            uint total = (uint)(body.Length + 6);
            var f = new byte[total];
            f[0] = (byte)(msgId >> 8); f[1] = (byte)msgId;
            f[2] = (byte)(total >> 24); f[3] = (byte)(total >> 16);
            f[4] = (byte)(total >> 8); f[5] = (byte)total;
            Buffer.BlockCopy(body, 0, f, 6, body.Length);
            return f;
        }

        /// Returns 1 (frame ready: msgId/body set, off advanced),
        /// 0 (need more data), -1 (protocol error).
        public static int Unframe(byte[] buf, int len, ref int off,
                                  out ushort msgId, out byte[] body)
        {
            msgId = 0; body = Empty;
            if (len - off < 6) return 0;
            msgId = (ushort)((buf[off] << 8) | buf[off + 1]);
            uint total = ((uint)buf[off + 2] << 24) | ((uint)buf[off + 3] << 16)
                       | ((uint)buf[off + 4] << 8) | buf[off + 5];
            if (total < 6 || total > MaxFrameSize) return -1;
            if (len - off < total) return 0;
            body = new byte[total - 6];
            Buffer.BlockCopy(buf, off + 6, body, 0, (int)(total - 6));
            off += (int)total;
            return 1;
        }
    }

    public class NfReader
    {
        public byte[] D; public int P; public int End; public bool Ok = true;
        public NfReader(byte[] d, int off, int len) { D = d; P = off; End = off + len; }
        public bool Done() { return P >= End; }
        public ulong Varint()
        {
            ulong v = 0; int shift = 0;
            while (P < End && shift <= 63)
            {
                byte b = D[P++];
                v |= (ulong)(b & 0x7F) << shift;
                if ((b & 0x80) == 0) return v;
                shift += 7;
            }
            Ok = false; return 0;
        }
        public float F32()
        {
            if (End - P < 4) { Ok = false; return 0; }
            var b = new byte[4]; Buffer.BlockCopy(D, P, b, 0, 4); P += 4;
            if (!BitConverter.IsLittleEndian) Array.Reverse(b);
            return BitConverter.ToSingle(b, 0);
        }
        public double F64()
        {
            if (End - P < 8) { Ok = false; return 0; }
            var b = new byte[8]; Buffer.BlockCopy(D, P, b, 0, 8); P += 8;
            if (!BitConverter.IsLittleEndian) Array.Reverse(b);
            return BitConverter.ToDouble(b, 0);
        }
        public byte[] Bytes()
        {
            ulong n = Varint();
            if (!Ok || (ulong)(End - P) < n) { Ok = false; return Nf.Empty; }
            var s = new byte[n]; Buffer.BlockCopy(D, P, s, 0, (int)n); P += (int)n;
            return s;
        }
        public void Skip(uint wt)
        {
            switch (wt)
            {
                case 0: Varint(); break;
                case 1: P += 8; break;
                case 2: { ulong n = Varint();
                          if ((ulong)(End - P) < n) Ok = false; else P += (int)n; break; }
                case 5: P += 4; break;
                default: Ok = false; break;
            }
            if (P > End) Ok = false;
        }
    }

    public class Ident
    {
        public long svrid = 0;
        public bool HasSvrid = false;
        public long index = 0;
        public bool HasIndex = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSvrid)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)svrid);
            }
            if (HasIndex)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)index);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            svrid = 0;
            HasSvrid = false;
            index = 0;
            HasIndex = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        svrid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasSvrid = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        index = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasIndex = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Vector2
    {
        public float x = 0f;
        public bool HasX = false;
        public float y = 0f;
        public bool HasY = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasX)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, x);
            }
            if (HasY)
            {
                Nf.PutTag(nf__o, 2, 5);
                Nf.PutF32(nf__o, y);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            x = 0f;
            HasX = false;
            y = 0f;
            HasY = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        x = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasX = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        y = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasY = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Vector3
    {
        public float x = 0f;
        public bool HasX = false;
        public float y = 0f;
        public bool HasY = false;
        public float z = 0f;
        public bool HasZ = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasX)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, x);
            }
            if (HasY)
            {
                Nf.PutTag(nf__o, 2, 5);
                Nf.PutF32(nf__o, y);
            }
            if (HasZ)
            {
                Nf.PutTag(nf__o, 3, 5);
                Nf.PutF32(nf__o, z);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            x = 0f;
            HasX = false;
            y = 0f;
            HasY = false;
            z = 0f;
            HasZ = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        x = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasX = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        y = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasY = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        z = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasZ = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class MsgBase
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public byte[] msg_data = Nf.Empty;
        public bool HasMsgData = false;
        public List<Ident> player_client_list = new List<Ident>();
        public Ident hash_ident = new Ident();
        public bool HasHashIdent = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasMsgData)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, msg_data);
            }
            foreach (var nf__it in player_client_list)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasHashIdent)
            {
                Nf.PutTag(nf__o, 4, 2);
                var nf__sub = new MemoryStream(); hash_ident.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            msg_data = Nf.Empty;
            HasMsgData = false;
            player_client_list.Clear();
            hash_ident = new Ident();
            HasHashIdent = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        msg_data = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMsgData = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_client_list.Add(nf__m);
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        hash_ident = nf__m; HasHashIdent = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Position
    {
        public float x = 0f;
        public bool HasX = false;
        public float y = 0f;
        public bool HasY = false;
        public float z = 0f;
        public bool HasZ = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasX)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, x);
            }
            if (HasY)
            {
                Nf.PutTag(nf__o, 2, 5);
                Nf.PutF32(nf__o, y);
            }
            if (HasZ)
            {
                Nf.PutTag(nf__o, 3, 5);
                Nf.PutF32(nf__o, z);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            x = 0f;
            HasX = false;
            y = 0f;
            HasY = false;
            z = 0f;
            HasZ = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        x = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasX = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        y = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasY = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        z = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasZ = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class PropertyInt
    {
        public byte[] property_name = Nf.Empty;
        public bool HasPropertyName = false;
        public long data = 0;
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasPropertyName)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, property_name);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)data);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            property_name = Nf.Empty;
            HasPropertyName = false;
            data = 0;
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        property_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasPropertyName = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        data = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class PropertyFloat
    {
        public byte[] property_name = Nf.Empty;
        public bool HasPropertyName = false;
        public float data = 0f;
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasPropertyName)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, property_name);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 2, 5);
                Nf.PutF32(nf__o, data);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            property_name = Nf.Empty;
            HasPropertyName = false;
            data = 0f;
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        property_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasPropertyName = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        data = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class PropertyString
    {
        public byte[] property_name = Nf.Empty;
        public bool HasPropertyName = false;
        public byte[] data = Nf.Empty;
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasPropertyName)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, property_name);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, data);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            property_name = Nf.Empty;
            HasPropertyName = false;
            data = Nf.Empty;
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        property_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasPropertyName = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        data = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class PropertyObject
    {
        public byte[] property_name = Nf.Empty;
        public bool HasPropertyName = false;
        public Ident data = new Ident();
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasPropertyName)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, property_name);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); data.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            property_name = Nf.Empty;
            HasPropertyName = false;
            data = new Ident();
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        property_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasPropertyName = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        data = nf__m; HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class PropertyVector2
    {
        public byte[] property_name = Nf.Empty;
        public bool HasPropertyName = false;
        public Vector2 data = new Vector2();
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasPropertyName)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, property_name);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); data.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            property_name = Nf.Empty;
            HasPropertyName = false;
            data = new Vector2();
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        property_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasPropertyName = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Vector2();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        data = nf__m; HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class PropertyVector3
    {
        public byte[] property_name = Nf.Empty;
        public bool HasPropertyName = false;
        public Vector3 data = new Vector3();
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasPropertyName)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, property_name);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); data.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            property_name = Nf.Empty;
            HasPropertyName = false;
            data = new Vector3();
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        property_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasPropertyName = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Vector3();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        data = nf__m; HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectPropertyList
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public List<PropertyInt> property_int_list = new List<PropertyInt>();
        public List<PropertyFloat> property_float_list = new List<PropertyFloat>();
        public List<PropertyString> property_string_list = new List<PropertyString>();
        public List<PropertyObject> property_object_list = new List<PropertyObject>();
        public List<PropertyVector2> property_vector2_list = new List<PropertyVector2>();
        public List<PropertyVector3> property_vector3_list = new List<PropertyVector3>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in property_int_list)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in property_float_list)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in property_string_list)
            {
                Nf.PutTag(nf__o, 4, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in property_object_list)
            {
                Nf.PutTag(nf__o, 5, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in property_vector2_list)
            {
                Nf.PutTag(nf__o, 6, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in property_vector3_list)
            {
                Nf.PutTag(nf__o, 7, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            property_int_list.Clear();
            property_float_list.Clear();
            property_string_list.Clear();
            property_object_list.Clear();
            property_vector2_list.Clear();
            property_vector3_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PropertyInt();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_int_list.Add(nf__m);
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PropertyFloat();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_float_list.Add(nf__m);
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PropertyString();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_string_list.Add(nf__m);
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PropertyObject();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_object_list.Add(nf__m);
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PropertyVector2();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_vector2_list.Add(nf__m);
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PropertyVector3();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_vector3_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectPropertyInt
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public List<PropertyInt> property_list = new List<PropertyInt>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in property_list)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            property_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PropertyInt();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectPropertyFloat
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public List<PropertyFloat> property_list = new List<PropertyFloat>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in property_list)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            property_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PropertyFloat();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectPropertyString
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public List<PropertyString> property_list = new List<PropertyString>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in property_list)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            property_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PropertyString();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectPropertyObject
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public List<PropertyObject> property_list = new List<PropertyObject>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in property_list)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            property_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PropertyObject();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectPropertyVector2
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public List<PropertyVector2> property_list = new List<PropertyVector2>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in property_list)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            property_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PropertyVector2();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectPropertyVector3
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public List<PropertyVector3> property_list = new List<PropertyVector3>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in property_list)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            property_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PropertyVector3();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class RecordInt
    {
        public int row = 0;
        public bool HasRow = false;
        public int col = 0;
        public bool HasCol = false;
        public long data = 0;
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasRow)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)row);
            }
            if (HasCol)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)col);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)data);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            row = 0;
            HasRow = false;
            col = 0;
            HasCol = false;
            data = 0;
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRow = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        col = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCol = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        data = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class RecordFloat
    {
        public int row = 0;
        public bool HasRow = false;
        public int col = 0;
        public bool HasCol = false;
        public float data = 0f;
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasRow)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)row);
            }
            if (HasCol)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)col);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 3, 5);
                Nf.PutF32(nf__o, data);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            row = 0;
            HasRow = false;
            col = 0;
            HasCol = false;
            data = 0f;
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRow = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        col = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCol = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        data = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class RecordString
    {
        public int row = 0;
        public bool HasRow = false;
        public int col = 0;
        public bool HasCol = false;
        public byte[] data = Nf.Empty;
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasRow)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)row);
            }
            if (HasCol)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)col);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, data);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            row = 0;
            HasRow = false;
            col = 0;
            HasCol = false;
            data = Nf.Empty;
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRow = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        col = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCol = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        data = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class RecordObject
    {
        public int row = 0;
        public bool HasRow = false;
        public int col = 0;
        public bool HasCol = false;
        public Ident data = new Ident();
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasRow)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)row);
            }
            if (HasCol)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)col);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); data.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            row = 0;
            HasRow = false;
            col = 0;
            HasCol = false;
            data = new Ident();
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRow = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        col = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCol = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        data = nf__m; HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class RecordVector2
    {
        public int row = 0;
        public bool HasRow = false;
        public int col = 0;
        public bool HasCol = false;
        public Vector2 data = new Vector2();
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasRow)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)row);
            }
            if (HasCol)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)col);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); data.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            row = 0;
            HasRow = false;
            col = 0;
            HasCol = false;
            data = new Vector2();
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRow = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        col = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCol = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Vector2();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        data = nf__m; HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class RecordVector3
    {
        public int row = 0;
        public bool HasRow = false;
        public int col = 0;
        public bool HasCol = false;
        public Vector3 data = new Vector3();
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasRow)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)row);
            }
            if (HasCol)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)col);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); data.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            row = 0;
            HasRow = false;
            col = 0;
            HasCol = false;
            data = new Vector3();
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRow = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        col = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCol = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Vector3();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        data = nf__m; HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class RecordAddRowStruct
    {
        public int row = 0;
        public bool HasRow = false;
        public List<RecordInt> record_int_list = new List<RecordInt>();
        public List<RecordFloat> record_float_list = new List<RecordFloat>();
        public List<RecordString> record_string_list = new List<RecordString>();
        public List<RecordObject> record_object_list = new List<RecordObject>();
        public List<RecordVector2> record_vector2_list = new List<RecordVector2>();
        public List<RecordVector3> record_vector3_list = new List<RecordVector3>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasRow)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)row);
            }
            foreach (var nf__it in record_int_list)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in record_float_list)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in record_string_list)
            {
                Nf.PutTag(nf__o, 4, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in record_object_list)
            {
                Nf.PutTag(nf__o, 5, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in record_vector2_list)
            {
                Nf.PutTag(nf__o, 6, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in record_vector3_list)
            {
                Nf.PutTag(nf__o, 7, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            row = 0;
            HasRow = false;
            record_int_list.Clear();
            record_float_list.Clear();
            record_string_list.Clear();
            record_object_list.Clear();
            record_vector2_list.Clear();
            record_vector3_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRow = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordInt();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        record_int_list.Add(nf__m);
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordFloat();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        record_float_list.Add(nf__m);
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordString();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        record_string_list.Add(nf__m);
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordObject();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        record_object_list.Add(nf__m);
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordVector2();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        record_vector2_list.Add(nf__m);
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordVector3();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        record_vector3_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectRecordBase
    {
        public byte[] record_name = Nf.Empty;
        public bool HasRecordName = false;
        public List<RecordAddRowStruct> row_struct = new List<RecordAddRowStruct>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasRecordName)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, record_name);
            }
            foreach (var nf__it in row_struct)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            record_name = Nf.Empty;
            HasRecordName = false;
            row_struct.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        record_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasRecordName = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordAddRowStruct();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        row_struct.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectRecordList
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public List<ObjectRecordBase> record_list = new List<ObjectRecordBase>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in record_list)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            record_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new ObjectRecordBase();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        record_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectRecordInt
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public byte[] record_name = Nf.Empty;
        public bool HasRecordName = false;
        public List<RecordInt> property_list = new List<RecordInt>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasRecordName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, record_name);
            }
            foreach (var nf__it in property_list)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            record_name = Nf.Empty;
            HasRecordName = false;
            property_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        record_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasRecordName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordInt();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectRecordFloat
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public byte[] record_name = Nf.Empty;
        public bool HasRecordName = false;
        public List<RecordFloat> property_list = new List<RecordFloat>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasRecordName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, record_name);
            }
            foreach (var nf__it in property_list)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            record_name = Nf.Empty;
            HasRecordName = false;
            property_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        record_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasRecordName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordFloat();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectRecordString
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public byte[] record_name = Nf.Empty;
        public bool HasRecordName = false;
        public List<RecordString> property_list = new List<RecordString>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasRecordName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, record_name);
            }
            foreach (var nf__it in property_list)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            record_name = Nf.Empty;
            HasRecordName = false;
            property_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        record_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasRecordName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordString();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectRecordObject
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public byte[] record_name = Nf.Empty;
        public bool HasRecordName = false;
        public List<RecordObject> property_list = new List<RecordObject>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasRecordName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, record_name);
            }
            foreach (var nf__it in property_list)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            record_name = Nf.Empty;
            HasRecordName = false;
            property_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        record_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasRecordName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordObject();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectRecordVector2
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public byte[] record_name = Nf.Empty;
        public bool HasRecordName = false;
        public List<RecordVector2> property_list = new List<RecordVector2>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasRecordName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, record_name);
            }
            foreach (var nf__it in property_list)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            record_name = Nf.Empty;
            HasRecordName = false;
            property_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        record_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasRecordName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordVector2();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectRecordVector3
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public byte[] record_name = Nf.Empty;
        public bool HasRecordName = false;
        public List<RecordVector3> property_list = new List<RecordVector3>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasRecordName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, record_name);
            }
            foreach (var nf__it in property_list)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            record_name = Nf.Empty;
            HasRecordName = false;
            property_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        record_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasRecordName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordVector3();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        property_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectRecordSwap
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public byte[] origin_record_name = Nf.Empty;
        public bool HasOriginRecordName = false;
        public byte[] target_record_name = Nf.Empty;
        public bool HasTargetRecordName = false;
        public int row_origin = 0;
        public bool HasRowOrigin = false;
        public int row_target = 0;
        public bool HasRowTarget = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasOriginRecordName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, origin_record_name);
            }
            if (HasTargetRecordName)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, target_record_name);
            }
            if (HasRowOrigin)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)row_origin);
            }
            if (HasRowTarget)
            {
                Nf.PutTag(nf__o, 5, 0);
                Nf.PutI64(nf__o, (long)row_target);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            origin_record_name = Nf.Empty;
            HasOriginRecordName = false;
            target_record_name = Nf.Empty;
            HasTargetRecordName = false;
            row_origin = 0;
            HasRowOrigin = false;
            row_target = 0;
            HasRowTarget = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        origin_record_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasOriginRecordName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        target_record_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasTargetRecordName = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row_origin = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRowOrigin = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row_target = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRowTarget = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectRecordAddRow
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public byte[] record_name = Nf.Empty;
        public bool HasRecordName = false;
        public List<RecordAddRowStruct> row_data = new List<RecordAddRowStruct>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasRecordName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, record_name);
            }
            foreach (var nf__it in row_data)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            record_name = Nf.Empty;
            HasRecordName = false;
            row_data.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        record_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasRecordName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RecordAddRowStruct();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        row_data.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ObjectRecordRemove
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public byte[] record_name = Nf.Empty;
        public bool HasRecordName = false;
        public List<int> remove_row = new List<int>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasRecordName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, record_name);
            }
            foreach (var nf__it in remove_row)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)nf__it);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            record_name = Nf.Empty;
            HasRecordName = false;
            remove_row.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        record_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasRecordName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        remove_row.Add((int)nf__r.Varint());
                        if (!nf__r.Ok) return false;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ServerInfoExt
    {
        public List<byte[]> key = new List<byte[]>();
        public List<byte[]> value = new List<byte[]>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in key)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, nf__it);
            }
            foreach (var nf__it in value)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, nf__it);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            key.Clear();
            value.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        key.Add(nf__r.Bytes());
                        if (!nf__r.Ok) return false;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        value.Add(nf__r.Bytes());
                        if (!nf__r.Ok) return false;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ServerInfoReport
    {
        public int server_id = 0;
        public bool HasServerId = false;
        public byte[] server_name = Nf.Empty;
        public bool HasServerName = false;
        public byte[] server_ip = Nf.Empty;
        public bool HasServerIp = false;
        public int server_port = 0;
        public bool HasServerPort = false;
        public int server_max_online = 0;
        public bool HasServerMaxOnline = false;
        public int server_cur_count = 0;
        public bool HasServerCurCount = false;
        public int server_state = 0;
        public bool HasServerState = false;
        public int server_type = 0;
        public bool HasServerType = false;
        public ServerInfoExt server_info_list_ext = new ServerInfoExt();
        public bool HasServerInfoListExt = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasServerId)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)server_id);
            }
            if (HasServerName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, server_name);
            }
            if (HasServerIp)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, server_ip);
            }
            if (HasServerPort)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)server_port);
            }
            if (HasServerMaxOnline)
            {
                Nf.PutTag(nf__o, 5, 0);
                Nf.PutI64(nf__o, (long)server_max_online);
            }
            if (HasServerCurCount)
            {
                Nf.PutTag(nf__o, 6, 0);
                Nf.PutI64(nf__o, (long)server_cur_count);
            }
            if (HasServerState)
            {
                Nf.PutTag(nf__o, 7, 0);
                Nf.PutI64(nf__o, (long)server_state);
            }
            if (HasServerType)
            {
                Nf.PutTag(nf__o, 8, 0);
                Nf.PutI64(nf__o, (long)server_type);
            }
            if (HasServerInfoListExt)
            {
                Nf.PutTag(nf__o, 9, 2);
                var nf__sub = new MemoryStream(); server_info_list_ext.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            server_id = 0;
            HasServerId = false;
            server_name = Nf.Empty;
            HasServerName = false;
            server_ip = Nf.Empty;
            HasServerIp = false;
            server_port = 0;
            HasServerPort = false;
            server_max_online = 0;
            HasServerMaxOnline = false;
            server_cur_count = 0;
            HasServerCurCount = false;
            server_state = 0;
            HasServerState = false;
            server_type = 0;
            HasServerType = false;
            server_info_list_ext = new ServerInfoExt();
            HasServerInfoListExt = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        server_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasServerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        server_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasServerName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        server_ip = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasServerIp = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        server_port = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasServerPort = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        server_max_online = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasServerMaxOnline = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        server_cur_count = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasServerCurCount = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        server_state = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasServerState = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        server_type = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasServerType = true;
                        break;
                    }
                    case 9:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new ServerInfoExt();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        server_info_list_ext = nf__m; HasServerInfoListExt = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ServerInfoReportList
    {
        public List<ServerInfoReport> server_list = new List<ServerInfoReport>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in server_list)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            server_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new ServerInfoReport();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        server_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AckEventResult
    {
        public int event_code = 0;
        public bool HasEventCode = false;
        public Ident event_object = new Ident();
        public bool HasEventObject = false;
        public Ident event_client = new Ident();
        public bool HasEventClient = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasEventCode)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)event_code);
            }
            if (HasEventObject)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); event_object.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasEventClient)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); event_client.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            event_code = 0;
            HasEventCode = false;
            event_object = new Ident();
            HasEventObject = false;
            event_client = new Ident();
            HasEventClient = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        event_code = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEventCode = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        event_object = nf__m; HasEventObject = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        event_client = nf__m; HasEventClient = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAccountLogin
    {
        public byte[] account = Nf.Empty;
        public bool HasAccount = false;
        public byte[] password = Nf.Empty;
        public bool HasPassword = false;
        public byte[] security_code = Nf.Empty;
        public bool HasSecurityCode = false;
        public byte[] sign_buff = Nf.Empty;
        public bool HasSignBuff = false;
        public int client_version = 0;
        public bool HasClientVersion = false;
        public int login_mode = 0;
        public bool HasLoginMode = false;
        public int client_ip = 0;
        public bool HasClientIp = false;
        public long client_mac = 0;
        public bool HasClientMac = false;
        public byte[] device_info = Nf.Empty;
        public bool HasDeviceInfo = false;
        public byte[] extra_info = Nf.Empty;
        public bool HasExtraInfo = false;
        public int platform_type = 0;
        public bool HasPlatformType = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasAccount)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, account);
            }
            if (HasPassword)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, password);
            }
            if (HasSecurityCode)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, security_code);
            }
            if (HasSignBuff)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, sign_buff);
            }
            if (HasClientVersion)
            {
                Nf.PutTag(nf__o, 6, 0);
                Nf.PutI64(nf__o, (long)client_version);
            }
            if (HasLoginMode)
            {
                Nf.PutTag(nf__o, 7, 0);
                Nf.PutI64(nf__o, (long)login_mode);
            }
            if (HasClientIp)
            {
                Nf.PutTag(nf__o, 8, 0);
                Nf.PutI64(nf__o, (long)client_ip);
            }
            if (HasClientMac)
            {
                Nf.PutTag(nf__o, 9, 0);
                Nf.PutI64(nf__o, (long)client_mac);
            }
            if (HasDeviceInfo)
            {
                Nf.PutTag(nf__o, 10, 2);
                Nf.PutBytes(nf__o, device_info);
            }
            if (HasExtraInfo)
            {
                Nf.PutTag(nf__o, 11, 2);
                Nf.PutBytes(nf__o, extra_info);
            }
            if (HasPlatformType)
            {
                Nf.PutTag(nf__o, 12, 0);
                Nf.PutI64(nf__o, (long)platform_type);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            account = Nf.Empty;
            HasAccount = false;
            password = Nf.Empty;
            HasPassword = false;
            security_code = Nf.Empty;
            HasSecurityCode = false;
            sign_buff = Nf.Empty;
            HasSignBuff = false;
            client_version = 0;
            HasClientVersion = false;
            login_mode = 0;
            HasLoginMode = false;
            client_ip = 0;
            HasClientIp = false;
            client_mac = 0;
            HasClientMac = false;
            device_info = Nf.Empty;
            HasDeviceInfo = false;
            extra_info = Nf.Empty;
            HasExtraInfo = false;
            platform_type = 0;
            HasPlatformType = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        account = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasAccount = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        password = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasPassword = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        security_code = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasSecurityCode = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        sign_buff = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasSignBuff = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        client_version = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasClientVersion = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        login_mode = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasLoginMode = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        client_ip = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasClientIp = true;
                        break;
                    }
                    case 9:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        client_mac = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasClientMac = true;
                        break;
                    }
                    case 10:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        device_info = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasDeviceInfo = true;
                        break;
                    }
                    case 11:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        extra_info = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasExtraInfo = true;
                        break;
                    }
                    case 12:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        platform_type = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasPlatformType = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ServerInfo
    {
        public int server_id = 0;
        public bool HasServerId = false;
        public byte[] name = Nf.Empty;
        public bool HasName = false;
        public int wait_count = 0;
        public bool HasWaitCount = false;
        public int status = 0;
        public bool HasStatus = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasServerId)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)server_id);
            }
            if (HasName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, name);
            }
            if (HasWaitCount)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)wait_count);
            }
            if (HasStatus)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)status);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            server_id = 0;
            HasServerId = false;
            name = Nf.Empty;
            HasName = false;
            wait_count = 0;
            HasWaitCount = false;
            status = 0;
            HasStatus = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        server_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasServerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        wait_count = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasWaitCount = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        status = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasStatus = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqServerList
    {
        public int type = 0;
        public bool HasType = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasType)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)type);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            type = 0;
            HasType = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        type = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasType = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AckServerList
    {
        public int type = 0;
        public bool HasType = false;
        public List<ServerInfo> info = new List<ServerInfo>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasType)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)type);
            }
            foreach (var nf__it in info)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            type = 0;
            HasType = false;
            info.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        type = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasType = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new ServerInfo();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        info.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqConnectWorld
    {
        public int world_id = 0;
        public bool HasWorldId = false;
        public byte[] account = Nf.Empty;
        public bool HasAccount = false;
        public Ident sender = new Ident();
        public bool HasSender = false;
        public int login_id = 0;
        public bool HasLoginId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasWorldId)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)world_id);
            }
            if (HasAccount)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, account);
            }
            if (HasSender)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); sender.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasLoginId)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)login_id);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            world_id = 0;
            HasWorldId = false;
            account = Nf.Empty;
            HasAccount = false;
            sender = new Ident();
            HasSender = false;
            login_id = 0;
            HasLoginId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        world_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasWorldId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        account = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasAccount = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        sender = nf__m; HasSender = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        login_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasLoginId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AckConnectWorldResult
    {
        public int world_id = 0;
        public bool HasWorldId = false;
        public Ident sender = new Ident();
        public bool HasSender = false;
        public int login_id = 0;
        public bool HasLoginId = false;
        public byte[] account = Nf.Empty;
        public bool HasAccount = false;
        public byte[] world_ip = Nf.Empty;
        public bool HasWorldIp = false;
        public int world_port = 0;
        public bool HasWorldPort = false;
        public byte[] world_key = Nf.Empty;
        public bool HasWorldKey = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasWorldId)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)world_id);
            }
            if (HasSender)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); sender.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasLoginId)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)login_id);
            }
            if (HasAccount)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, account);
            }
            if (HasWorldIp)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, world_ip);
            }
            if (HasWorldPort)
            {
                Nf.PutTag(nf__o, 6, 0);
                Nf.PutI64(nf__o, (long)world_port);
            }
            if (HasWorldKey)
            {
                Nf.PutTag(nf__o, 7, 2);
                Nf.PutBytes(nf__o, world_key);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            world_id = 0;
            HasWorldId = false;
            sender = new Ident();
            HasSender = false;
            login_id = 0;
            HasLoginId = false;
            account = Nf.Empty;
            HasAccount = false;
            world_ip = Nf.Empty;
            HasWorldIp = false;
            world_port = 0;
            HasWorldPort = false;
            world_key = Nf.Empty;
            HasWorldKey = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        world_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasWorldId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        sender = nf__m; HasSender = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        login_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasLoginId = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        account = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasAccount = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        world_ip = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasWorldIp = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        world_port = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasWorldPort = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        world_key = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasWorldKey = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqSelectServer
    {
        public int world_id = 0;
        public bool HasWorldId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasWorldId)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)world_id);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            world_id = 0;
            HasWorldId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        world_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasWorldId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqRoleList
    {
        public int game_id = 0;
        public bool HasGameId = false;
        public byte[] account = Nf.Empty;
        public bool HasAccount = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasGameId)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)game_id);
            }
            if (HasAccount)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, account);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            game_id = 0;
            HasGameId = false;
            account = Nf.Empty;
            HasAccount = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        game_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGameId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        account = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasAccount = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class RoleLiteInfo
    {
        public Ident id = new Ident();
        public bool HasId = false;
        public int career = 0;
        public bool HasCareer = false;
        public int sex = 0;
        public bool HasSex = false;
        public int race = 0;
        public bool HasRace = false;
        public byte[] noob_name = Nf.Empty;
        public bool HasNoobName = false;
        public int game_id = 0;
        public bool HasGameId = false;
        public int role_level = 0;
        public bool HasRoleLevel = false;
        public int delete_time = 0;
        public bool HasDeleteTime = false;
        public int reg_time = 0;
        public bool HasRegTime = false;
        public int last_offline_time = 0;
        public bool HasLastOfflineTime = false;
        public int last_offline_ip = 0;
        public bool HasLastOfflineIp = false;
        public byte[] view_record = Nf.Empty;
        public bool HasViewRecord = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasCareer)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)career);
            }
            if (HasSex)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)sex);
            }
            if (HasRace)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)race);
            }
            if (HasNoobName)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, noob_name);
            }
            if (HasGameId)
            {
                Nf.PutTag(nf__o, 6, 0);
                Nf.PutI64(nf__o, (long)game_id);
            }
            if (HasRoleLevel)
            {
                Nf.PutTag(nf__o, 7, 0);
                Nf.PutI64(nf__o, (long)role_level);
            }
            if (HasDeleteTime)
            {
                Nf.PutTag(nf__o, 8, 0);
                Nf.PutI64(nf__o, (long)delete_time);
            }
            if (HasRegTime)
            {
                Nf.PutTag(nf__o, 9, 0);
                Nf.PutI64(nf__o, (long)reg_time);
            }
            if (HasLastOfflineTime)
            {
                Nf.PutTag(nf__o, 10, 0);
                Nf.PutI64(nf__o, (long)last_offline_time);
            }
            if (HasLastOfflineIp)
            {
                Nf.PutTag(nf__o, 11, 0);
                Nf.PutI64(nf__o, (long)last_offline_ip);
            }
            if (HasViewRecord)
            {
                Nf.PutTag(nf__o, 12, 2);
                Nf.PutBytes(nf__o, view_record);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            id = new Ident();
            HasId = false;
            career = 0;
            HasCareer = false;
            sex = 0;
            HasSex = false;
            race = 0;
            HasRace = false;
            noob_name = Nf.Empty;
            HasNoobName = false;
            game_id = 0;
            HasGameId = false;
            role_level = 0;
            HasRoleLevel = false;
            delete_time = 0;
            HasDeleteTime = false;
            reg_time = 0;
            HasRegTime = false;
            last_offline_time = 0;
            HasLastOfflineTime = false;
            last_offline_ip = 0;
            HasLastOfflineIp = false;
            view_record = Nf.Empty;
            HasViewRecord = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        id = nf__m; HasId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        career = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCareer = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        sex = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasSex = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        race = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRace = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        noob_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasNoobName = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        game_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGameId = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        role_level = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRoleLevel = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        delete_time = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasDeleteTime = true;
                        break;
                    }
                    case 9:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        reg_time = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRegTime = true;
                        break;
                    }
                    case 10:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        last_offline_time = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasLastOfflineTime = true;
                        break;
                    }
                    case 11:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        last_offline_ip = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasLastOfflineIp = true;
                        break;
                    }
                    case 12:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        view_record = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasViewRecord = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AckRoleLiteInfoList
    {
        public List<RoleLiteInfo> char_data = new List<RoleLiteInfo>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in char_data)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            char_data.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new RoleLiteInfo();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        char_data.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqCreateRole
    {
        public byte[] account = Nf.Empty;
        public bool HasAccount = false;
        public int career = 0;
        public bool HasCareer = false;
        public int sex = 0;
        public bool HasSex = false;
        public int race = 0;
        public bool HasRace = false;
        public byte[] noob_name = Nf.Empty;
        public bool HasNoobName = false;
        public int game_id = 0;
        public bool HasGameId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasAccount)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, account);
            }
            if (HasCareer)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)career);
            }
            if (HasSex)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)sex);
            }
            if (HasRace)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)race);
            }
            if (HasNoobName)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, noob_name);
            }
            if (HasGameId)
            {
                Nf.PutTag(nf__o, 6, 0);
                Nf.PutI64(nf__o, (long)game_id);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            account = Nf.Empty;
            HasAccount = false;
            career = 0;
            HasCareer = false;
            sex = 0;
            HasSex = false;
            race = 0;
            HasRace = false;
            noob_name = Nf.Empty;
            HasNoobName = false;
            game_id = 0;
            HasGameId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        account = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasAccount = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        career = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCareer = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        sex = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasSex = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        race = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRace = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        noob_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasNoobName = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        game_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGameId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqDeleteRole
    {
        public byte[] account = Nf.Empty;
        public bool HasAccount = false;
        public byte[] name = Nf.Empty;
        public bool HasName = false;
        public int game_id = 0;
        public bool HasGameId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasAccount)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, account);
            }
            if (HasName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, name);
            }
            if (HasGameId)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)game_id);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            account = Nf.Empty;
            HasAccount = false;
            name = Nf.Empty;
            HasName = false;
            game_id = 0;
            HasGameId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        account = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasAccount = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        game_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGameId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ServerHeartBeat
    {
        public int count = 0;
        public bool HasCount = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasCount)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)count);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            count = 0;
            HasCount = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        count = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCount = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class BatchPropertySync
    {
        public byte[] class_name = Nf.Empty;
        public bool HasClassName = false;
        public byte[] property_name = Nf.Empty;
        public bool HasPropertyName = false;
        public int ptype = 0;
        public bool HasPtype = false;
        public int count = 0;
        public bool HasCount = false;
        public byte[] svrid = Nf.Empty;
        public bool HasSvrid = false;
        public byte[] index = Nf.Empty;
        public bool HasIndex = false;
        public byte[] data = Nf.Empty;
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasClassName)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, class_name);
            }
            if (HasPropertyName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, property_name);
            }
            if (HasPtype)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)ptype);
            }
            if (HasCount)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)count);
            }
            if (HasSvrid)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, svrid);
            }
            if (HasIndex)
            {
                Nf.PutTag(nf__o, 6, 2);
                Nf.PutBytes(nf__o, index);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 7, 2);
                Nf.PutBytes(nf__o, data);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            class_name = Nf.Empty;
            HasClassName = false;
            property_name = Nf.Empty;
            HasPropertyName = false;
            ptype = 0;
            HasPtype = false;
            count = 0;
            HasCount = false;
            svrid = Nf.Empty;
            HasSvrid = false;
            index = Nf.Empty;
            HasIndex = false;
            data = Nf.Empty;
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        class_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasClassName = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        property_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasPropertyName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        ptype = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasPtype = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        count = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCount = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        svrid = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasSvrid = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        index = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasIndex = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        data = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class BatchPropertyColumns
    {
        public byte[] class_name = Nf.Empty;
        public bool HasClassName = false;
        public List<byte[]> names = new List<byte[]>();
        public int ptype = 0;
        public bool HasPtype = false;
        public byte[] counts = Nf.Empty;
        public bool HasCounts = false;
        public byte[] svrid = Nf.Empty;
        public bool HasSvrid = false;
        public byte[] index = Nf.Empty;
        public bool HasIndex = false;
        public byte[] data = Nf.Empty;
        public bool HasData = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasClassName)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, class_name);
            }
            foreach (var nf__it in names)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, nf__it);
            }
            if (HasPtype)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)ptype);
            }
            if (HasCounts)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, counts);
            }
            if (HasSvrid)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, svrid);
            }
            if (HasIndex)
            {
                Nf.PutTag(nf__o, 6, 2);
                Nf.PutBytes(nf__o, index);
            }
            if (HasData)
            {
                Nf.PutTag(nf__o, 7, 2);
                Nf.PutBytes(nf__o, data);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            class_name = Nf.Empty;
            HasClassName = false;
            names.Clear();
            ptype = 0;
            HasPtype = false;
            counts = Nf.Empty;
            HasCounts = false;
            svrid = Nf.Empty;
            HasSvrid = false;
            index = Nf.Empty;
            HasIndex = false;
            data = Nf.Empty;
            HasData = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        class_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasClassName = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        names.Add(nf__r.Bytes());
                        if (!nf__r.Ok) return false;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        ptype = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasPtype = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        counts = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasCounts = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        svrid = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasSvrid = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        index = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasIndex = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        data = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasData = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class InterestPosSync
    {
        public float scale = 0f;
        public bool HasScale = false;
        public int count = 0;
        public bool HasCount = false;
        public byte[] svrid = Nf.Empty;
        public bool HasSvrid = false;
        public byte[] index = Nf.Empty;
        public bool HasIndex = false;
        public byte[] qpos = Nf.Empty;
        public bool HasQpos = false;
        public byte[] gone_svrid = Nf.Empty;
        public bool HasGoneSvrid = false;
        public byte[] gone_index = Nf.Empty;
        public bool HasGoneIndex = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasScale)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, scale);
            }
            if (HasCount)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)count);
            }
            if (HasSvrid)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, svrid);
            }
            if (HasIndex)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, index);
            }
            if (HasQpos)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, qpos);
            }
            if (HasGoneSvrid)
            {
                Nf.PutTag(nf__o, 6, 2);
                Nf.PutBytes(nf__o, gone_svrid);
            }
            if (HasGoneIndex)
            {
                Nf.PutTag(nf__o, 7, 2);
                Nf.PutBytes(nf__o, gone_index);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            scale = 0f;
            HasScale = false;
            count = 0;
            HasCount = false;
            svrid = Nf.Empty;
            HasSvrid = false;
            index = Nf.Empty;
            HasIndex = false;
            qpos = Nf.Empty;
            HasQpos = false;
            gone_svrid = Nf.Empty;
            HasGoneSvrid = false;
            gone_index = Nf.Empty;
            HasGoneIndex = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        scale = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasScale = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        count = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCount = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        svrid = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasSvrid = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        index = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasIndex = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        qpos = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasQpos = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        gone_svrid = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasGoneSvrid = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        gone_index = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasGoneIndex = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqSwitchServer
    {
        public Ident selfid = new Ident();
        public bool HasSelfid = false;
        public long self_serverid = 0;
        public bool HasSelfServerid = false;
        public long target_serverid = 0;
        public bool HasTargetServerid = false;
        public long gate_serverid = 0;
        public bool HasGateServerid = false;
        public long scene_id = 0;
        public bool HasSceneId = false;
        public Ident client_id = new Ident();
        public bool HasClientId = false;
        public long group_id = 0;
        public bool HasGroupId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSelfid)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); selfid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasSelfServerid)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)self_serverid);
            }
            if (HasTargetServerid)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)target_serverid);
            }
            if (HasGateServerid)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)gate_serverid);
            }
            if (HasSceneId)
            {
                Nf.PutTag(nf__o, 5, 0);
                Nf.PutI64(nf__o, (long)scene_id);
            }
            if (HasClientId)
            {
                Nf.PutTag(nf__o, 6, 2);
                var nf__sub = new MemoryStream(); client_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasGroupId)
            {
                Nf.PutTag(nf__o, 7, 0);
                Nf.PutI64(nf__o, (long)group_id);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            selfid = new Ident();
            HasSelfid = false;
            self_serverid = 0;
            HasSelfServerid = false;
            target_serverid = 0;
            HasTargetServerid = false;
            gate_serverid = 0;
            HasGateServerid = false;
            scene_id = 0;
            HasSceneId = false;
            client_id = new Ident();
            HasClientId = false;
            group_id = 0;
            HasGroupId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        selfid = nf__m; HasSelfid = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        self_serverid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasSelfServerid = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        target_serverid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasTargetServerid = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        gate_serverid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGateServerid = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        scene_id = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasSceneId = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        client_id = nf__m; HasClientId = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        group_id = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGroupId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AckSwitchServer
    {
        public Ident selfid = new Ident();
        public bool HasSelfid = false;
        public long self_serverid = 0;
        public bool HasSelfServerid = false;
        public long target_serverid = 0;
        public bool HasTargetServerid = false;
        public long gate_serverid = 0;
        public bool HasGateServerid = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSelfid)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); selfid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasSelfServerid)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)self_serverid);
            }
            if (HasTargetServerid)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)target_serverid);
            }
            if (HasGateServerid)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)gate_serverid);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            selfid = new Ident();
            HasSelfid = false;
            self_serverid = 0;
            HasSelfServerid = false;
            target_serverid = 0;
            HasTargetServerid = false;
            gate_serverid = 0;
            HasGateServerid = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        selfid = nf__m; HasSelfid = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        self_serverid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasSelfServerid = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        target_serverid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasTargetServerid = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        gate_serverid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGateServerid = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class SwitchServerData
    {
        public Ident selfid = new Ident();
        public bool HasSelfid = false;
        public byte[] account = Nf.Empty;
        public bool HasAccount = false;
        public byte[] name = Nf.Empty;
        public bool HasName = false;
        public byte[] blob = Nf.Empty;
        public bool HasBlob = false;
        public long target_serverid = 0;
        public bool HasTargetServerid = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSelfid)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); selfid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasAccount)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, account);
            }
            if (HasName)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, name);
            }
            if (HasBlob)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, blob);
            }
            if (HasTargetServerid)
            {
                Nf.PutTag(nf__o, 5, 0);
                Nf.PutI64(nf__o, (long)target_serverid);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            selfid = new Ident();
            HasSelfid = false;
            account = Nf.Empty;
            HasAccount = false;
            name = Nf.Empty;
            HasName = false;
            blob = Nf.Empty;
            HasBlob = false;
            target_serverid = 0;
            HasTargetServerid = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        selfid = nf__m; HasSelfid = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        account = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasAccount = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasName = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        blob = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasBlob = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        target_serverid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasTargetServerid = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqSetFightHero
    {
        public Ident selfid = new Ident();
        public bool HasSelfid = false;
        public Ident heroid = new Ident();
        public bool HasHeroid = false;
        public int fight_pos = 0;
        public bool HasFightPos = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSelfid)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); selfid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasHeroid)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); heroid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasFightPos)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)fight_pos);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            selfid = new Ident();
            HasSelfid = false;
            heroid = new Ident();
            HasHeroid = false;
            fight_pos = 0;
            HasFightPos = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        selfid = nf__m; HasSelfid = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        heroid = nf__m; HasHeroid = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        fight_pos = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasFightPos = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class RoleOnlineNotify
    {
        public Ident guild = new Ident();
        public bool HasGuild = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasGuild)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); guild.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            guild = new Ident();
            HasGuild = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        guild = nf__m; HasGuild = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class RoleOfflineNotify
    {
        public Ident guild = new Ident();
        public bool HasGuild = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasGuild)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); guild.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            guild = new Ident();
            HasGuild = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        guild = nf__m; HasGuild = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class SwitchNotice
    {
        public int code = 0;
        public bool HasCode = false;
        public long target_serverid = 0;
        public bool HasTargetServerid = false;
        public long retry_after_ms = 0;
        public bool HasRetryAfterMs = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasCode)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)code);
            }
            if (HasTargetServerid)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)target_serverid);
            }
            if (HasRetryAfterMs)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)retry_after_ms);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            code = 0;
            HasCode = false;
            target_serverid = 0;
            HasTargetServerid = false;
            retry_after_ms = 0;
            HasRetryAfterMs = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        code = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCode = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        target_serverid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasTargetServerid = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        retry_after_ms = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRetryAfterMs = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class SessionBindNotify
    {
        public Ident selfid = new Ident();
        public bool HasSelfid = false;
        public byte[] account = Nf.Empty;
        public bool HasAccount = false;
        public byte[] name = Nf.Empty;
        public bool HasName = false;
        public Ident client_id = new Ident();
        public bool HasClientId = false;
        public long scene_id = 0;
        public bool HasSceneId = false;
        public long group_id = 0;
        public bool HasGroupId = false;
        public byte[] save_key = Nf.Empty;
        public bool HasSaveKey = false;
        public long game_id = 0;
        public bool HasGameId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSelfid)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); selfid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasAccount)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, account);
            }
            if (HasName)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, name);
            }
            if (HasClientId)
            {
                Nf.PutTag(nf__o, 4, 2);
                var nf__sub = new MemoryStream(); client_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasSceneId)
            {
                Nf.PutTag(nf__o, 5, 0);
                Nf.PutI64(nf__o, (long)scene_id);
            }
            if (HasGroupId)
            {
                Nf.PutTag(nf__o, 6, 0);
                Nf.PutI64(nf__o, (long)group_id);
            }
            if (HasSaveKey)
            {
                Nf.PutTag(nf__o, 7, 2);
                Nf.PutBytes(nf__o, save_key);
            }
            if (HasGameId)
            {
                Nf.PutTag(nf__o, 8, 0);
                Nf.PutI64(nf__o, (long)game_id);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            selfid = new Ident();
            HasSelfid = false;
            account = Nf.Empty;
            HasAccount = false;
            name = Nf.Empty;
            HasName = false;
            client_id = new Ident();
            HasClientId = false;
            scene_id = 0;
            HasSceneId = false;
            group_id = 0;
            HasGroupId = false;
            save_key = Nf.Empty;
            HasSaveKey = false;
            game_id = 0;
            HasGameId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        selfid = nf__m; HasSelfid = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        account = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasAccount = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasName = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        client_id = nf__m; HasClientId = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        scene_id = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasSceneId = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        group_id = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGroupId = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        save_key = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasSaveKey = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        game_id = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGameId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class SwitchRefused
    {
        public Ident selfid = new Ident();
        public bool HasSelfid = false;
        public long self_serverid = 0;
        public bool HasSelfServerid = false;
        public long target_serverid = 0;
        public bool HasTargetServerid = false;
        public int result = 0;
        public bool HasResult = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSelfid)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); selfid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasSelfServerid)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)self_serverid);
            }
            if (HasTargetServerid)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)target_serverid);
            }
            if (HasResult)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)result);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            selfid = new Ident();
            HasSelfid = false;
            self_serverid = 0;
            HasSelfServerid = false;
            target_serverid = 0;
            HasTargetServerid = false;
            result = 0;
            HasResult = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        selfid = nf__m; HasSelfid = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        self_serverid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasSelfServerid = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        target_serverid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasTargetServerid = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        result = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasResult = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqEnterGameServer
    {
        public Ident id = new Ident();
        public bool HasId = false;
        public byte[] account = Nf.Empty;
        public bool HasAccount = false;
        public int game_id = 0;
        public bool HasGameId = false;
        public byte[] name = Nf.Empty;
        public bool HasName = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasAccount)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, account);
            }
            if (HasGameId)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)game_id);
            }
            if (HasName)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, name);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            id = new Ident();
            HasId = false;
            account = Nf.Empty;
            HasAccount = false;
            game_id = 0;
            HasGameId = false;
            name = Nf.Empty;
            HasName = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        id = nf__m; HasId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        account = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasAccount = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        game_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGameId = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasName = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class PlayerEntryInfo
    {
        public Ident object_guid = new Ident();
        public bool HasObjectGuid = false;
        public float x = 0f;
        public bool HasX = false;
        public float y = 0f;
        public bool HasY = false;
        public float z = 0f;
        public bool HasZ = false;
        public int career_type = 0;
        public bool HasCareerType = false;
        public int player_state = 0;
        public bool HasPlayerState = false;
        public byte[] config_id = Nf.Empty;
        public bool HasConfigId = false;
        public int scene_id = 0;
        public bool HasSceneId = false;
        public byte[] class_id = Nf.Empty;
        public bool HasClassId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasObjectGuid)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); object_guid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasX)
            {
                Nf.PutTag(nf__o, 2, 5);
                Nf.PutF32(nf__o, x);
            }
            if (HasY)
            {
                Nf.PutTag(nf__o, 3, 5);
                Nf.PutF32(nf__o, y);
            }
            if (HasZ)
            {
                Nf.PutTag(nf__o, 4, 5);
                Nf.PutF32(nf__o, z);
            }
            if (HasCareerType)
            {
                Nf.PutTag(nf__o, 5, 0);
                Nf.PutI64(nf__o, (long)career_type);
            }
            if (HasPlayerState)
            {
                Nf.PutTag(nf__o, 6, 0);
                Nf.PutI64(nf__o, (long)player_state);
            }
            if (HasConfigId)
            {
                Nf.PutTag(nf__o, 7, 2);
                Nf.PutBytes(nf__o, config_id);
            }
            if (HasSceneId)
            {
                Nf.PutTag(nf__o, 8, 0);
                Nf.PutI64(nf__o, (long)scene_id);
            }
            if (HasClassId)
            {
                Nf.PutTag(nf__o, 9, 2);
                Nf.PutBytes(nf__o, class_id);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            object_guid = new Ident();
            HasObjectGuid = false;
            x = 0f;
            HasX = false;
            y = 0f;
            HasY = false;
            z = 0f;
            HasZ = false;
            career_type = 0;
            HasCareerType = false;
            player_state = 0;
            HasPlayerState = false;
            config_id = Nf.Empty;
            HasConfigId = false;
            scene_id = 0;
            HasSceneId = false;
            class_id = Nf.Empty;
            HasClassId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        object_guid = nf__m; HasObjectGuid = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        x = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasX = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        y = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasY = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        z = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasZ = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        career_type = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCareerType = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        player_state = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasPlayerState = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        config_id = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasConfigId = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        scene_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasSceneId = true;
                        break;
                    }
                    case 9:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        class_id = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasClassId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AckPlayerEntryList
    {
        public List<PlayerEntryInfo> object_list = new List<PlayerEntryInfo>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in object_list)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            object_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PlayerEntryInfo();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        object_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AckPlayerLeaveList
    {
        public List<Ident> object_list = new List<Ident>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in object_list)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            object_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        object_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckPlayerMove
    {
        public Ident mover = new Ident();
        public bool HasMover = false;
        public int move_type = 0;
        public bool HasMoveType = false;
        public List<Position> target_pos = new List<Position>();
        public List<Position> source_pos = new List<Position>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasMover)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); mover.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasMoveType)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)move_type);
            }
            foreach (var nf__it in target_pos)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in source_pos)
            {
                Nf.PutTag(nf__o, 4, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            mover = new Ident();
            HasMover = false;
            move_type = 0;
            HasMoveType = false;
            target_pos.Clear();
            source_pos.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        mover = nf__m; HasMover = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        move_type = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasMoveType = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Position();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        target_pos.Add(nf__m);
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Position();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        source_pos.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ChatContainer
    {
        public int container_type = 0;
        public bool HasContainerType = false;
        public byte[] data_info = Nf.Empty;
        public bool HasDataInfo = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasContainerType)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)container_type);
            }
            if (HasDataInfo)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, data_info);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            container_type = 0;
            HasContainerType = false;
            data_info = Nf.Empty;
            HasDataInfo = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        container_type = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasContainerType = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        data_info = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasDataInfo = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckPlayerChat
    {
        public Ident chat_id = new Ident();
        public bool HasChatId = false;
        public int chat_type = 0;
        public bool HasChatType = false;
        public byte[] chat_info = Nf.Empty;
        public bool HasChatInfo = false;
        public byte[] chat_name = Nf.Empty;
        public bool HasChatName = false;
        public Ident target_id = new Ident();
        public bool HasTargetId = false;
        public List<ChatContainer> container_data = new List<ChatContainer>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasChatId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); chat_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasChatType)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)chat_type);
            }
            if (HasChatInfo)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, chat_info);
            }
            if (HasChatName)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, chat_name);
            }
            if (HasTargetId)
            {
                Nf.PutTag(nf__o, 5, 2);
                var nf__sub = new MemoryStream(); target_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in container_data)
            {
                Nf.PutTag(nf__o, 6, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            chat_id = new Ident();
            HasChatId = false;
            chat_type = 0;
            HasChatType = false;
            chat_info = Nf.Empty;
            HasChatInfo = false;
            chat_name = Nf.Empty;
            HasChatName = false;
            target_id = new Ident();
            HasTargetId = false;
            container_data.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        chat_id = nf__m; HasChatId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        chat_type = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasChatType = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        chat_info = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasChatInfo = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        chat_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasChatName = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        target_id = nf__m; HasTargetId = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new ChatContainer();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        container_data.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class EffectData
    {
        public Ident effect_ident = new Ident();
        public bool HasEffectIdent = false;
        public int effect_value = 0;
        public bool HasEffectValue = false;
        public int effect_rlt = 0;
        public bool HasEffectRlt = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasEffectIdent)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); effect_ident.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasEffectValue)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)effect_value);
            }
            if (HasEffectRlt)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)effect_rlt);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            effect_ident = new Ident();
            HasEffectIdent = false;
            effect_value = 0;
            HasEffectValue = false;
            effect_rlt = 0;
            HasEffectRlt = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        effect_ident = nf__m; HasEffectIdent = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        effect_value = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEffectValue = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        effect_rlt = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEffectRlt = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckUseSkill
    {
        public Ident user = new Ident();
        public bool HasUser = false;
        public byte[] skill_id = Nf.Empty;
        public bool HasSkillId = false;
        public Position now_pos = new Position();
        public bool HasNowPos = false;
        public Position tar_pos = new Position();
        public bool HasTarPos = false;
        public int use_index = 0;
        public bool HasUseIndex = false;
        public List<EffectData> effect_data = new List<EffectData>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasUser)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); user.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasSkillId)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, skill_id);
            }
            if (HasNowPos)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); now_pos.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasTarPos)
            {
                Nf.PutTag(nf__o, 4, 2);
                var nf__sub = new MemoryStream(); tar_pos.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasUseIndex)
            {
                Nf.PutTag(nf__o, 5, 0);
                Nf.PutI64(nf__o, (long)use_index);
            }
            foreach (var nf__it in effect_data)
            {
                Nf.PutTag(nf__o, 6, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            user = new Ident();
            HasUser = false;
            skill_id = Nf.Empty;
            HasSkillId = false;
            now_pos = new Position();
            HasNowPos = false;
            tar_pos = new Position();
            HasTarPos = false;
            use_index = 0;
            HasUseIndex = false;
            effect_data.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        user = nf__m; HasUser = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        skill_id = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasSkillId = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Position();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        now_pos = nf__m; HasNowPos = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Position();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        tar_pos = nf__m; HasTarPos = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        use_index = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasUseIndex = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new EffectData();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        effect_data.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckSwapScene
    {
        public int transfer_type = 0;
        public bool HasTransferType = false;
        public int scene_id = 0;
        public bool HasSceneId = false;
        public int line_id = 0;
        public bool HasLineId = false;
        public float x = 0f;
        public bool HasX = false;
        public float y = 0f;
        public bool HasY = false;
        public float z = 0f;
        public bool HasZ = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasTransferType)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)transfer_type);
            }
            if (HasSceneId)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)scene_id);
            }
            if (HasLineId)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)line_id);
            }
            if (HasX)
            {
                Nf.PutTag(nf__o, 4, 5);
                Nf.PutF32(nf__o, x);
            }
            if (HasY)
            {
                Nf.PutTag(nf__o, 5, 5);
                Nf.PutF32(nf__o, y);
            }
            if (HasZ)
            {
                Nf.PutTag(nf__o, 6, 5);
                Nf.PutF32(nf__o, z);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            transfer_type = 0;
            HasTransferType = false;
            scene_id = 0;
            HasSceneId = false;
            line_id = 0;
            HasLineId = false;
            x = 0f;
            HasX = false;
            y = 0f;
            HasY = false;
            z = 0f;
            HasZ = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        transfer_type = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasTransferType = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        scene_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasSceneId = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        line_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasLineId = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        x = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasX = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        y = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasY = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        z = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasZ = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ItemStruct
    {
        public byte[] item_id = Nf.Empty;
        public bool HasItemId = false;
        public int item_count = 0;
        public bool HasItemCount = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasItemId)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, item_id);
            }
            if (HasItemCount)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)item_count);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            item_id = Nf.Empty;
            HasItemId = false;
            item_count = 0;
            HasItemCount = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        item_id = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasItemId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        item_count = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasItemCount = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckUseItem
    {
        public Ident user = new Ident();
        public bool HasUser = false;
        public Ident item_guid = new Ident();
        public bool HasItemGuid = false;
        public List<EffectData> effect_data = new List<EffectData>();
        public ItemStruct item = new ItemStruct();
        public bool HasItem = false;
        public Ident targetid = new Ident();
        public bool HasTargetid = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasUser)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); user.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasItemGuid)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); item_guid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in effect_data)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasItem)
            {
                Nf.PutTag(nf__o, 4, 2);
                var nf__sub = new MemoryStream(); item.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasTargetid)
            {
                Nf.PutTag(nf__o, 5, 2);
                var nf__sub = new MemoryStream(); targetid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            user = new Ident();
            HasUser = false;
            item_guid = new Ident();
            HasItemGuid = false;
            effect_data.Clear();
            item = new ItemStruct();
            HasItem = false;
            targetid = new Ident();
            HasTargetid = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        user = nf__m; HasUser = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        item_guid = nf__m; HasItemGuid = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new EffectData();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        effect_data.Add(nf__m);
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new ItemStruct();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        item = nf__m; HasItem = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        targetid = nf__m; HasTargetid = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqWearEquip
    {
        public Ident selfid = new Ident();
        public bool HasSelfid = false;
        public Ident equipid = new Ident();
        public bool HasEquipid = false;
        public Ident target_id = new Ident();
        public bool HasTargetId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSelfid)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); selfid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasEquipid)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); equipid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasTargetId)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); target_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            selfid = new Ident();
            HasSelfid = false;
            equipid = new Ident();
            HasEquipid = false;
            target_id = new Ident();
            HasTargetId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        selfid = nf__m; HasSelfid = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        equipid = nf__m; HasEquipid = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        target_id = nf__m; HasTargetId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class TakeOffEquip
    {
        public Ident selfid = new Ident();
        public bool HasSelfid = false;
        public Ident equipid = new Ident();
        public bool HasEquipid = false;
        public Ident target_id = new Ident();
        public bool HasTargetId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSelfid)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); selfid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasEquipid)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); equipid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasTargetId)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); target_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            selfid = new Ident();
            HasSelfid = false;
            equipid = new Ident();
            HasEquipid = false;
            target_id = new Ident();
            HasTargetId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        selfid = nf__m; HasSelfid = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        equipid = nf__m; HasEquipid = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        target_id = nf__m; HasTargetId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAcceptTask
    {
        public byte[] task_id = Nf.Empty;
        public bool HasTaskId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasTaskId)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, task_id);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            task_id = Nf.Empty;
            HasTaskId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        task_id = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasTaskId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqCompeleteTask
    {
        public byte[] task_id = Nf.Empty;
        public bool HasTaskId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasTaskId)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, task_id);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            task_id = Nf.Empty;
            HasTaskId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        task_id = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasTaskId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class TeammemberInfo
    {
        public Ident player_id = new Ident();
        public bool HasPlayerId = false;
        public byte[] name = Nf.Empty;
        public bool HasName = false;
        public int nLevel = 0;
        public bool HasNLevel = false;
        public int job = 0;
        public bool HasJob = false;
        public byte[] HeadIcon = Nf.Empty;
        public bool HasHeadIcon = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasPlayerId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); player_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, name);
            }
            if (HasNLevel)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)nLevel);
            }
            if (HasJob)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)job);
            }
            if (HasHeadIcon)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, HeadIcon);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            player_id = new Ident();
            HasPlayerId = false;
            name = Nf.Empty;
            HasName = false;
            nLevel = 0;
            HasNLevel = false;
            job = 0;
            HasJob = false;
            HeadIcon = Nf.Empty;
            HasHeadIcon = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        player_id = nf__m; HasPlayerId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nLevel = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNLevel = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        job = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasJob = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        HeadIcon = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasHeadIcon = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class TeamInfo
    {
        public Ident team_id = new Ident();
        public bool HasTeamId = false;
        public Ident captain_id = new Ident();
        public bool HasCaptainId = false;
        public List<TeammemberInfo> teammemberInfo = new List<TeammemberInfo>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasTeamId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); team_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasCaptainId)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); captain_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in teammemberInfo)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            team_id = new Ident();
            HasTeamId = false;
            captain_id = new Ident();
            HasCaptainId = false;
            teammemberInfo.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        team_id = nf__m; HasTeamId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        captain_id = nf__m; HasCaptainId = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new TeammemberInfo();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        teammemberInfo.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckCreateTeam
    {
        public Ident team_id = new Ident();
        public bool HasTeamId = false;
        public TeamInfo xTeamInfo = new TeamInfo();
        public bool HasXTeamInfo = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasTeamId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); team_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasXTeamInfo)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); xTeamInfo.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            team_id = new Ident();
            HasTeamId = false;
            xTeamInfo = new TeamInfo();
            HasXTeamInfo = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        team_id = nf__m; HasTeamId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new TeamInfo();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xTeamInfo = nf__m; HasXTeamInfo = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckJoinTeam
    {
        public Ident team_id = new Ident();
        public bool HasTeamId = false;
        public TeamInfo xTeamInfo = new TeamInfo();
        public bool HasXTeamInfo = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasTeamId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); team_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasXTeamInfo)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); xTeamInfo.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            team_id = new Ident();
            HasTeamId = false;
            xTeamInfo = new TeamInfo();
            HasXTeamInfo = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        team_id = nf__m; HasTeamId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new TeamInfo();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xTeamInfo = nf__m; HasXTeamInfo = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckLeaveTeam
    {
        public Ident team_id = new Ident();
        public bool HasTeamId = false;
        public TeamInfo xTeamInfo = new TeamInfo();
        public bool HasXTeamInfo = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasTeamId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); team_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasXTeamInfo)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); xTeamInfo.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            team_id = new Ident();
            HasTeamId = false;
            xTeamInfo = new TeamInfo();
            HasXTeamInfo = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        team_id = nf__m; HasTeamId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new TeamInfo();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xTeamInfo = nf__m; HasXTeamInfo = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckOprTeamMember
    {
        public Ident team_id = new Ident();
        public bool HasTeamId = false;
        public Ident member_id = new Ident();
        public bool HasMemberId = false;
        public int type = 0;
        public bool HasType = false;
        public TeamInfo xTeamInfo = new TeamInfo();
        public bool HasXTeamInfo = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasTeamId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); team_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasMemberId)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); member_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasType)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)type);
            }
            if (HasXTeamInfo)
            {
                Nf.PutTag(nf__o, 4, 2);
                var nf__sub = new MemoryStream(); xTeamInfo.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            team_id = new Ident();
            HasTeamId = false;
            member_id = new Ident();
            HasMemberId = false;
            type = 0;
            HasType = false;
            xTeamInfo = new TeamInfo();
            HasXTeamInfo = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        team_id = nf__m; HasTeamId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        member_id = nf__m; HasMemberId = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        type = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasType = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new TeamInfo();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xTeamInfo = nf__m; HasXTeamInfo = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckCreateGuild
    {
        public Ident guild_id = new Ident();
        public bool HasGuildId = false;
        public byte[] guild_name = Nf.Empty;
        public bool HasGuildName = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasGuildId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); guild_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasGuildName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, guild_name);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            guild_id = new Ident();
            HasGuildId = false;
            guild_name = Nf.Empty;
            HasGuildName = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        guild_id = nf__m; HasGuildId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        guild_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasGuildName = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckJoinGuild
    {
        public Ident guild_id = new Ident();
        public bool HasGuildId = false;
        public byte[] guild_name = Nf.Empty;
        public bool HasGuildName = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasGuildId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); guild_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasGuildName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, guild_name);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            guild_id = new Ident();
            HasGuildId = false;
            guild_name = Nf.Empty;
            HasGuildName = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        guild_id = nf__m; HasGuildId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        guild_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasGuildName = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckLeaveGuild
    {
        public Ident guild_id = new Ident();
        public bool HasGuildId = false;
        public byte[] guild_name = Nf.Empty;
        public bool HasGuildName = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasGuildId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); guild_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasGuildName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, guild_name);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            guild_id = new Ident();
            HasGuildId = false;
            guild_name = Nf.Empty;
            HasGuildName = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        guild_id = nf__m; HasGuildId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        guild_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasGuildName = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqSearchGuild
    {
        public byte[] guild_name = Nf.Empty;
        public bool HasGuildName = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasGuildName)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, guild_name);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            guild_name = Nf.Empty;
            HasGuildName = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        guild_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasGuildName = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqCommand
    {
        public Ident control_id = new Ident();
        public bool HasControlId = false;
        public int command_id = 0;
        public bool HasCommandId = false;
        public byte[] command_str_value = Nf.Empty;
        public bool HasCommandStrValue = false;
        public long command_value_int = 0;
        public bool HasCommandValueInt = false;
        public double command_value_float = 0d;
        public bool HasCommandValueFloat = false;
        public byte[] command_value_str = Nf.Empty;
        public bool HasCommandValueStr = false;
        public Ident command_value_object = new Ident();
        public bool HasCommandValueObject = false;
        public int row = 0;
        public bool HasRow = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasControlId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); control_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasCommandId)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)command_id);
            }
            if (HasCommandStrValue)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, command_str_value);
            }
            if (HasCommandValueInt)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)command_value_int);
            }
            if (HasCommandValueFloat)
            {
                Nf.PutTag(nf__o, 5, 1);
                Nf.PutF64(nf__o, command_value_float);
            }
            if (HasCommandValueStr)
            {
                Nf.PutTag(nf__o, 6, 2);
                Nf.PutBytes(nf__o, command_value_str);
            }
            if (HasCommandValueObject)
            {
                Nf.PutTag(nf__o, 7, 2);
                var nf__sub = new MemoryStream(); command_value_object.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasRow)
            {
                Nf.PutTag(nf__o, 8, 0);
                Nf.PutI64(nf__o, (long)row);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            control_id = new Ident();
            HasControlId = false;
            command_id = 0;
            HasCommandId = false;
            command_str_value = Nf.Empty;
            HasCommandStrValue = false;
            command_value_int = 0;
            HasCommandValueInt = false;
            command_value_float = 0d;
            HasCommandValueFloat = false;
            command_value_str = Nf.Empty;
            HasCommandValueStr = false;
            command_value_object = new Ident();
            HasCommandValueObject = false;
            row = 0;
            HasRow = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        control_id = nf__m; HasControlId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        command_id = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCommandId = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        command_str_value = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasCommandStrValue = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        command_value_int = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCommandValueInt = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 1)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        command_value_float = nf__r.F64();
                        if (!nf__r.Ok) return false;
                        HasCommandValueFloat = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        command_value_str = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasCommandValueStr = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        command_value_object = nf__m; HasCommandValueObject = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRow = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class PVPRoomInfo
    {
        public int nCellStatus = 0;
        public bool HasNCellStatus = false;
        public Ident RoomID = new Ident();
        public bool HasRoomID = false;
        public int nPVPMode = 0;
        public bool HasNPVPMode = false;
        public int nPVPGrade = 0;
        public bool HasNPVPGrade = false;
        public int MaxPalyer = 0;
        public bool HasMaxPalyer = false;
        public List<Ident> xRedPlayer = new List<Ident>();
        public List<Ident> xBluePlayer = new List<Ident>();
        public long serverid = 0;
        public bool HasServerid = false;
        public long SceneID = 0;
        public bool HasSceneID = false;
        public long groupID = 0;
        public bool HasGroupID = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasNCellStatus)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)nCellStatus);
            }
            if (HasRoomID)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); RoomID.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasNPVPMode)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)nPVPMode);
            }
            if (HasNPVPGrade)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)nPVPGrade);
            }
            if (HasMaxPalyer)
            {
                Nf.PutTag(nf__o, 5, 0);
                Nf.PutI64(nf__o, (long)MaxPalyer);
            }
            foreach (var nf__it in xRedPlayer)
            {
                Nf.PutTag(nf__o, 6, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            foreach (var nf__it in xBluePlayer)
            {
                Nf.PutTag(nf__o, 7, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasServerid)
            {
                Nf.PutTag(nf__o, 8, 0);
                Nf.PutI64(nf__o, (long)serverid);
            }
            if (HasSceneID)
            {
                Nf.PutTag(nf__o, 9, 0);
                Nf.PutI64(nf__o, (long)SceneID);
            }
            if (HasGroupID)
            {
                Nf.PutTag(nf__o, 10, 0);
                Nf.PutI64(nf__o, (long)groupID);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            nCellStatus = 0;
            HasNCellStatus = false;
            RoomID = new Ident();
            HasRoomID = false;
            nPVPMode = 0;
            HasNPVPMode = false;
            nPVPGrade = 0;
            HasNPVPGrade = false;
            MaxPalyer = 0;
            HasMaxPalyer = false;
            xRedPlayer.Clear();
            xBluePlayer.Clear();
            serverid = 0;
            HasServerid = false;
            SceneID = 0;
            HasSceneID = false;
            groupID = 0;
            HasGroupID = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nCellStatus = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNCellStatus = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        RoomID = nf__m; HasRoomID = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nPVPMode = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNPVPMode = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nPVPGrade = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNPVPGrade = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MaxPalyer = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasMaxPalyer = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xRedPlayer.Add(nf__m);
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xBluePlayer.Add(nf__m);
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        serverid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasServerid = true;
                        break;
                    }
                    case 9:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        SceneID = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasSceneID = true;
                        break;
                    }
                    case 10:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        groupID = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGroupID = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqPVPApplyMatch
    {
        public Ident self_id = new Ident();
        public bool HasSelfId = false;
        public int nPVPMode = 0;
        public bool HasNPVPMode = false;
        public long score = 0;
        public bool HasScore = false;
        public int ApplyType = 0;
        public bool HasApplyType = false;
        public Ident team_id = new Ident();
        public bool HasTeamId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSelfId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); self_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasNPVPMode)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)nPVPMode);
            }
            if (HasScore)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)score);
            }
            if (HasApplyType)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)ApplyType);
            }
            if (HasTeamId)
            {
                Nf.PutTag(nf__o, 5, 2);
                var nf__sub = new MemoryStream(); team_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            self_id = new Ident();
            HasSelfId = false;
            nPVPMode = 0;
            HasNPVPMode = false;
            score = 0;
            HasScore = false;
            ApplyType = 0;
            HasApplyType = false;
            team_id = new Ident();
            HasTeamId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        self_id = nf__m; HasSelfId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nPVPMode = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNPVPMode = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        score = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasScore = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        ApplyType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasApplyType = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        team_id = nf__m; HasTeamId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AckPVPApplyMatch
    {
        public Ident self_id = new Ident();
        public bool HasSelfId = false;
        public PVPRoomInfo xRoomInfo = new PVPRoomInfo();
        public bool HasXRoomInfo = false;
        public int ApplyType = 0;
        public bool HasApplyType = false;
        public int nResult = 0;
        public bool HasNResult = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSelfId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); self_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasXRoomInfo)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); xRoomInfo.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasApplyType)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)ApplyType);
            }
            if (HasNResult)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)nResult);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            self_id = new Ident();
            HasSelfId = false;
            xRoomInfo = new PVPRoomInfo();
            HasXRoomInfo = false;
            ApplyType = 0;
            HasApplyType = false;
            nResult = 0;
            HasNResult = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        self_id = nf__m; HasSelfId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PVPRoomInfo();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xRoomInfo = nf__m; HasXRoomInfo = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        ApplyType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasApplyType = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nResult = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNResult = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqCreatePVPEctype
    {
        public Ident self_id = new Ident();
        public bool HasSelfId = false;
        public PVPRoomInfo xRoomInfo = new PVPRoomInfo();
        public bool HasXRoomInfo = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSelfId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); self_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasXRoomInfo)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); xRoomInfo.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            self_id = new Ident();
            HasSelfId = false;
            xRoomInfo = new PVPRoomInfo();
            HasXRoomInfo = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        self_id = nf__m; HasSelfId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PVPRoomInfo();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xRoomInfo = nf__m; HasXRoomInfo = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AckCreatePVPEctype
    {
        public Ident self_id = new Ident();
        public bool HasSelfId = false;
        public PVPRoomInfo xRoomInfo = new PVPRoomInfo();
        public bool HasXRoomInfo = false;
        public int ApplyType = 0;
        public bool HasApplyType = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasSelfId)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); self_id.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasXRoomInfo)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); xRoomInfo.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasApplyType)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)ApplyType);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            self_id = new Ident();
            HasSelfId = false;
            xRoomInfo = new PVPRoomInfo();
            HasXRoomInfo = false;
            ApplyType = 0;
            HasApplyType = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        self_id = nf__m; HasSelfId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new PVPRoomInfo();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xRoomInfo = nf__m; HasXRoomInfo = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        ApplyType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasApplyType = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class SearchGuildObject
    {
        public Ident guild_ID = new Ident();
        public bool HasGuildID = false;
        public byte[] guild_name = Nf.Empty;
        public bool HasGuildName = false;
        public byte[] guild_icon = Nf.Empty;
        public bool HasGuildIcon = false;
        public int guild_member_count = 0;
        public bool HasGuildMemberCount = false;
        public int guild_member_max_count = 0;
        public bool HasGuildMemberMaxCount = false;
        public int guild_honor = 0;
        public bool HasGuildHonor = false;
        public int guild_rank = 0;
        public bool HasGuildRank = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasGuildID)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); guild_ID.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasGuildName)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, guild_name);
            }
            if (HasGuildIcon)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, guild_icon);
            }
            if (HasGuildMemberCount)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)guild_member_count);
            }
            if (HasGuildMemberMaxCount)
            {
                Nf.PutTag(nf__o, 5, 0);
                Nf.PutI64(nf__o, (long)guild_member_max_count);
            }
            if (HasGuildHonor)
            {
                Nf.PutTag(nf__o, 6, 0);
                Nf.PutI64(nf__o, (long)guild_honor);
            }
            if (HasGuildRank)
            {
                Nf.PutTag(nf__o, 7, 0);
                Nf.PutI64(nf__o, (long)guild_rank);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            guild_ID = new Ident();
            HasGuildID = false;
            guild_name = Nf.Empty;
            HasGuildName = false;
            guild_icon = Nf.Empty;
            HasGuildIcon = false;
            guild_member_count = 0;
            HasGuildMemberCount = false;
            guild_member_max_count = 0;
            HasGuildMemberMaxCount = false;
            guild_honor = 0;
            HasGuildHonor = false;
            guild_rank = 0;
            HasGuildRank = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        guild_ID = nf__m; HasGuildID = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        guild_name = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasGuildName = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        guild_icon = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasGuildIcon = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        guild_member_count = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGuildMemberCount = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        guild_member_max_count = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGuildMemberMaxCount = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        guild_honor = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGuildHonor = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        guild_rank = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasGuildRank = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AckSearchGuild
    {
        public List<SearchGuildObject> guild_list = new List<SearchGuildObject>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in guild_list)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            guild_list.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new SearchGuildObject();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        guild_list.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class PackMysqlParam
    {
        public byte[] strRecordName = Nf.Empty;
        public bool HasStrRecordName = false;
        public byte[] strKey = Nf.Empty;
        public bool HasStrKey = false;
        public List<byte[]> fieldVecList = new List<byte[]>();
        public List<byte[]> valueVecList = new List<byte[]>();
        public long bExit = 0;
        public bool HasBExit = false;
        public long nreqid = 0;
        public bool HasNreqid = false;
        public long nRet = 0;
        public bool HasNRet = false;
        public long eType = 0;
        public bool HasEType = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasStrRecordName)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, strRecordName);
            }
            if (HasStrKey)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, strKey);
            }
            foreach (var nf__it in fieldVecList)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, nf__it);
            }
            foreach (var nf__it in valueVecList)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, nf__it);
            }
            if (HasBExit)
            {
                Nf.PutTag(nf__o, 5, 0);
                Nf.PutI64(nf__o, (long)bExit);
            }
            if (HasNreqid)
            {
                Nf.PutTag(nf__o, 6, 0);
                Nf.PutI64(nf__o, (long)nreqid);
            }
            if (HasNRet)
            {
                Nf.PutTag(nf__o, 7, 0);
                Nf.PutI64(nf__o, (long)nRet);
            }
            if (HasEType)
            {
                Nf.PutTag(nf__o, 8, 0);
                Nf.PutI64(nf__o, (long)eType);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            strRecordName = Nf.Empty;
            HasStrRecordName = false;
            strKey = Nf.Empty;
            HasStrKey = false;
            fieldVecList.Clear();
            valueVecList.Clear();
            bExit = 0;
            HasBExit = false;
            nreqid = 0;
            HasNreqid = false;
            nRet = 0;
            HasNRet = false;
            eType = 0;
            HasEType = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        strRecordName = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasStrRecordName = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        strKey = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasStrKey = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        fieldVecList.Add(nf__r.Bytes());
                        if (!nf__r.Ok) return false;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        valueVecList.Add(nf__r.Bytes());
                        if (!nf__r.Ok) return false;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        bExit = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasBExit = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nreqid = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNreqid = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nRet = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNRet = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        eType = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEType = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class PackMysqlServerInfo
    {
        public long nRconnectTime = 0;
        public bool HasNRconnectTime = false;
        public long nRconneCount = 0;
        public bool HasNRconneCount = false;
        public long nPort = 0;
        public bool HasNPort = false;
        public byte[] strDBName = Nf.Empty;
        public bool HasStrDBName = false;
        public byte[] strDnsIp = Nf.Empty;
        public bool HasStrDnsIp = false;
        public byte[] strDBUser = Nf.Empty;
        public bool HasStrDBUser = false;
        public byte[] strDBPwd = Nf.Empty;
        public bool HasStrDBPwd = false;
        public long nServerID = 0;
        public bool HasNServerID = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasNRconnectTime)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)nRconnectTime);
            }
            if (HasNRconneCount)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)nRconneCount);
            }
            if (HasNPort)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)nPort);
            }
            if (HasStrDBName)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, strDBName);
            }
            if (HasStrDnsIp)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, strDnsIp);
            }
            if (HasStrDBUser)
            {
                Nf.PutTag(nf__o, 6, 2);
                Nf.PutBytes(nf__o, strDBUser);
            }
            if (HasStrDBPwd)
            {
                Nf.PutTag(nf__o, 7, 2);
                Nf.PutBytes(nf__o, strDBPwd);
            }
            if (HasNServerID)
            {
                Nf.PutTag(nf__o, 8, 0);
                Nf.PutI64(nf__o, (long)nServerID);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            nRconnectTime = 0;
            HasNRconnectTime = false;
            nRconneCount = 0;
            HasNRconneCount = false;
            nPort = 0;
            HasNPort = false;
            strDBName = Nf.Empty;
            HasStrDBName = false;
            strDnsIp = Nf.Empty;
            HasStrDnsIp = false;
            strDBUser = Nf.Empty;
            HasStrDBUser = false;
            strDBPwd = Nf.Empty;
            HasStrDBPwd = false;
            nServerID = 0;
            HasNServerID = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nRconnectTime = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNRconnectTime = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nRconneCount = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNRconneCount = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nPort = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNPort = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        strDBName = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasStrDBName = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        strDnsIp = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasStrDnsIp = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        strDBUser = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasStrDBUser = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        strDBPwd = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasStrDBPwd = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nServerID = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNServerID = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class PackSURLParam
    {
        public byte[] strUrl = Nf.Empty;
        public bool HasStrUrl = false;
        public byte[] strGetParams = Nf.Empty;
        public bool HasStrGetParams = false;
        public byte[] strBodyData = Nf.Empty;
        public bool HasStrBodyData = false;
        public byte[] strCookies = Nf.Empty;
        public bool HasStrCookies = false;
        public double fTimeOutSec = 0d;
        public bool HasFTimeOutSec = false;
        public byte[] strRsp = Nf.Empty;
        public bool HasStrRsp = false;
        public long nRet = 0;
        public bool HasNRet = false;
        public long nReqID = 0;
        public bool HasNReqID = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasStrUrl)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, strUrl);
            }
            if (HasStrGetParams)
            {
                Nf.PutTag(nf__o, 2, 2);
                Nf.PutBytes(nf__o, strGetParams);
            }
            if (HasStrBodyData)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, strBodyData);
            }
            if (HasStrCookies)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, strCookies);
            }
            if (HasFTimeOutSec)
            {
                Nf.PutTag(nf__o, 5, 1);
                Nf.PutF64(nf__o, fTimeOutSec);
            }
            if (HasStrRsp)
            {
                Nf.PutTag(nf__o, 6, 2);
                Nf.PutBytes(nf__o, strRsp);
            }
            if (HasNRet)
            {
                Nf.PutTag(nf__o, 7, 0);
                Nf.PutI64(nf__o, (long)nRet);
            }
            if (HasNReqID)
            {
                Nf.PutTag(nf__o, 8, 0);
                Nf.PutI64(nf__o, (long)nReqID);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            strUrl = Nf.Empty;
            HasStrUrl = false;
            strGetParams = Nf.Empty;
            HasStrGetParams = false;
            strBodyData = Nf.Empty;
            HasStrBodyData = false;
            strCookies = Nf.Empty;
            HasStrCookies = false;
            fTimeOutSec = 0d;
            HasFTimeOutSec = false;
            strRsp = Nf.Empty;
            HasStrRsp = false;
            nRet = 0;
            HasNRet = false;
            nReqID = 0;
            HasNReqID = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        strUrl = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasStrUrl = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        strGetParams = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasStrGetParams = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        strBodyData = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasStrBodyData = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        strCookies = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasStrCookies = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 1)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        fTimeOutSec = nf__r.F64();
                        if (!nf__r.Ok) return false;
                        HasFTimeOutSec = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        strRsp = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasStrRsp = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nRet = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNRet = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        nReqID = (long)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasNReqID = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckBuyObjectFormShop
    {
        public byte[] config_id = Nf.Empty;
        public bool HasConfigId = false;
        public float x = 0f;
        public bool HasX = false;
        public float y = 0f;
        public bool HasY = false;
        public float z = 0f;
        public bool HasZ = false;
        public byte[] Shop_id = Nf.Empty;
        public bool HasShopId = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasConfigId)
            {
                Nf.PutTag(nf__o, 1, 2);
                Nf.PutBytes(nf__o, config_id);
            }
            if (HasX)
            {
                Nf.PutTag(nf__o, 2, 5);
                Nf.PutF32(nf__o, x);
            }
            if (HasY)
            {
                Nf.PutTag(nf__o, 3, 5);
                Nf.PutF32(nf__o, y);
            }
            if (HasZ)
            {
                Nf.PutTag(nf__o, 4, 5);
                Nf.PutF32(nf__o, z);
            }
            if (HasShopId)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, Shop_id);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            config_id = Nf.Empty;
            HasConfigId = false;
            x = 0f;
            HasX = false;
            y = 0f;
            HasY = false;
            z = 0f;
            HasZ = false;
            Shop_id = Nf.Empty;
            HasShopId = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        config_id = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasConfigId = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        x = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasX = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        y = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasY = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        z = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasZ = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        Shop_id = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasShopId = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqAckMoveBuildObject
    {
        public int row = 0;
        public bool HasRow = false;
        public Ident object_guid = new Ident();
        public bool HasObjectGuid = false;
        public float x = 0f;
        public bool HasX = false;
        public float y = 0f;
        public bool HasY = false;
        public float z = 0f;
        public bool HasZ = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasRow)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)row);
            }
            if (HasObjectGuid)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); object_guid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasX)
            {
                Nf.PutTag(nf__o, 3, 5);
                Nf.PutF32(nf__o, x);
            }
            if (HasY)
            {
                Nf.PutTag(nf__o, 4, 5);
                Nf.PutF32(nf__o, y);
            }
            if (HasZ)
            {
                Nf.PutTag(nf__o, 5, 5);
                Nf.PutF32(nf__o, z);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            row = 0;
            HasRow = false;
            object_guid = new Ident();
            HasObjectGuid = false;
            x = 0f;
            HasX = false;
            y = 0f;
            HasY = false;
            z = 0f;
            HasZ = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRow = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        object_guid = nf__m; HasObjectGuid = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        x = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasX = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        y = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasY = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        z = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasZ = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqUpBuildLv
    {
        public int row = 0;
        public bool HasRow = false;
        public Ident object_guid = new Ident();
        public bool HasObjectGuid = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasRow)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)row);
            }
            if (HasObjectGuid)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); object_guid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            row = 0;
            HasRow = false;
            object_guid = new Ident();
            HasObjectGuid = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRow = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        object_guid = nf__m; HasObjectGuid = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqCreateItem
    {
        public int row = 0;
        public bool HasRow = false;
        public Ident object_guid = new Ident();
        public bool HasObjectGuid = false;
        public byte[] config_id = Nf.Empty;
        public bool HasConfigId = false;
        public int count = 0;
        public bool HasCount = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasRow)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)row);
            }
            if (HasObjectGuid)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); object_guid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasConfigId)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, config_id);
            }
            if (HasCount)
            {
                Nf.PutTag(nf__o, 4, 0);
                Nf.PutI64(nf__o, (long)count);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            row = 0;
            HasRow = false;
            object_guid = new Ident();
            HasObjectGuid = false;
            config_id = Nf.Empty;
            HasConfigId = false;
            count = 0;
            HasCount = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRow = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        object_guid = nf__m; HasObjectGuid = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        config_id = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasConfigId = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        count = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCount = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ReqBuildOperate
    {
        public int row = 0;
        public bool HasRow = false;
        public Ident object_guid = new Ident();
        public bool HasObjectGuid = false;
        public int functype = 0;
        public bool HasFunctype = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasRow)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)row);
            }
            if (HasObjectGuid)
            {
                Nf.PutTag(nf__o, 2, 2);
                var nf__sub = new MemoryStream(); object_guid.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasFunctype)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)functype);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            row = 0;
            HasRow = false;
            object_guid = new Ident();
            HasObjectGuid = false;
            functype = 0;
            HasFunctype = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        row = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRow = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Ident();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        object_guid = nf__m; HasObjectGuid = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        functype = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasFunctype = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class FSVector3
    {
        public float x = 0f;
        public bool HasX = false;
        public float y = 0f;
        public bool HasY = false;
        public float z = 0f;
        public bool HasZ = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasX)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, x);
            }
            if (HasY)
            {
                Nf.PutTag(nf__o, 2, 5);
                Nf.PutF32(nf__o, y);
            }
            if (HasZ)
            {
                Nf.PutTag(nf__o, 3, 5);
                Nf.PutF32(nf__o, z);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            x = 0f;
            HasX = false;
            y = 0f;
            HasY = false;
            z = 0f;
            HasZ = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        x = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasX = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        y = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasY = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        z = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasZ = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Suwayyah
    {
        public int EventType = 0;
        public bool HasEventType = false;
        public float EventTime = 0f;
        public bool HasEventTime = false;
        public float EndTime = 0f;
        public bool HasEndTime = false;
        public float DamageRang = 0f;
        public bool HasDamageRang = false;
        public float BackHeroDis = 0f;
        public bool HasBackHeroDis = false;
        public float BackNpcDis = 0f;
        public bool HasBackNpcDis = false;
        public byte[] BeAttackParticle = Nf.Empty;
        public bool HasBeAttackParticle = false;
        public byte[] MethodCall = Nf.Empty;
        public bool HasMethodCall = false;
        public byte[] MethodParam = Nf.Empty;
        public bool HasMethodParam = false;
        public byte[] TargetMethodCall = Nf.Empty;
        public bool HasTargetMethodCall = false;
        public byte[] TargetMethodParam = Nf.Empty;
        public bool HasTargetMethodParam = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasEventType)
            {
                Nf.PutTag(nf__o, 1, 0);
                Nf.PutI64(nf__o, (long)EventType);
            }
            if (HasEventTime)
            {
                Nf.PutTag(nf__o, 2, 5);
                Nf.PutF32(nf__o, EventTime);
            }
            if (HasEndTime)
            {
                Nf.PutTag(nf__o, 3, 5);
                Nf.PutF32(nf__o, EndTime);
            }
            if (HasDamageRang)
            {
                Nf.PutTag(nf__o, 4, 5);
                Nf.PutF32(nf__o, DamageRang);
            }
            if (HasBackHeroDis)
            {
                Nf.PutTag(nf__o, 5, 5);
                Nf.PutF32(nf__o, BackHeroDis);
            }
            if (HasBackNpcDis)
            {
                Nf.PutTag(nf__o, 6, 5);
                Nf.PutF32(nf__o, BackNpcDis);
            }
            if (HasBeAttackParticle)
            {
                Nf.PutTag(nf__o, 7, 2);
                Nf.PutBytes(nf__o, BeAttackParticle);
            }
            if (HasMethodCall)
            {
                Nf.PutTag(nf__o, 8, 2);
                Nf.PutBytes(nf__o, MethodCall);
            }
            if (HasMethodParam)
            {
                Nf.PutTag(nf__o, 9, 2);
                Nf.PutBytes(nf__o, MethodParam);
            }
            if (HasTargetMethodCall)
            {
                Nf.PutTag(nf__o, 10, 2);
                Nf.PutBytes(nf__o, TargetMethodCall);
            }
            if (HasTargetMethodParam)
            {
                Nf.PutTag(nf__o, 11, 2);
                Nf.PutBytes(nf__o, TargetMethodParam);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            EventType = 0;
            HasEventType = false;
            EventTime = 0f;
            HasEventTime = false;
            EndTime = 0f;
            HasEndTime = false;
            DamageRang = 0f;
            HasDamageRang = false;
            BackHeroDis = 0f;
            HasBackHeroDis = false;
            BackNpcDis = 0f;
            HasBackNpcDis = false;
            BeAttackParticle = Nf.Empty;
            HasBeAttackParticle = false;
            MethodCall = Nf.Empty;
            HasMethodCall = false;
            MethodParam = Nf.Empty;
            HasMethodParam = false;
            TargetMethodCall = Nf.Empty;
            HasTargetMethodCall = false;
            TargetMethodParam = Nf.Empty;
            HasTargetMethodParam = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEventType = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasEventTime = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EndTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasEndTime = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        DamageRang = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasDamageRang = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BackHeroDis = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasBackHeroDis = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BackNpcDis = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasBackNpcDis = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BeAttackParticle = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasBeAttackParticle = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodCall = true;
                        break;
                    }
                    case 9:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodParam = true;
                        break;
                    }
                    case 10:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        TargetMethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasTargetMethodCall = true;
                        break;
                    }
                    case 11:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        TargetMethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasTargetMethodParam = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class SuwayyahEvents
    {
        public List<Suwayyah> xSuwayyahList = new List<Suwayyah>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in xSuwayyahList)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            xSuwayyahList.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Suwayyah();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xSuwayyahList.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class TacheBomp
    {
        public float BompTime = 0f;
        public bool HasBompTime = false;
        public float BompRang = 0f;
        public bool HasBompRang = false;
        public byte[] BompPrefabPath = Nf.Empty;
        public bool HasBompPrefabPath = false;
        public byte[] BeAttackParticle = Nf.Empty;
        public bool HasBeAttackParticle = false;
        public float BackNpcDis = 0f;
        public bool HasBackNpcDis = false;
        public float BackHeroDis = 0f;
        public bool HasBackHeroDis = false;
        public byte[] MethodCall = Nf.Empty;
        public bool HasMethodCall = false;
        public byte[] MethodParam = Nf.Empty;
        public bool HasMethodParam = false;
        public byte[] TargetMethodCall = Nf.Empty;
        public bool HasTargetMethodCall = false;
        public byte[] TargetMethodParam = Nf.Empty;
        public bool HasTargetMethodParam = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasBompTime)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, BompTime);
            }
            if (HasBompRang)
            {
                Nf.PutTag(nf__o, 2, 5);
                Nf.PutF32(nf__o, BompRang);
            }
            if (HasBompPrefabPath)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, BompPrefabPath);
            }
            if (HasBeAttackParticle)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, BeAttackParticle);
            }
            if (HasBackNpcDis)
            {
                Nf.PutTag(nf__o, 5, 5);
                Nf.PutF32(nf__o, BackNpcDis);
            }
            if (HasBackHeroDis)
            {
                Nf.PutTag(nf__o, 6, 5);
                Nf.PutF32(nf__o, BackHeroDis);
            }
            if (HasMethodCall)
            {
                Nf.PutTag(nf__o, 7, 2);
                Nf.PutBytes(nf__o, MethodCall);
            }
            if (HasMethodParam)
            {
                Nf.PutTag(nf__o, 8, 2);
                Nf.PutBytes(nf__o, MethodParam);
            }
            if (HasTargetMethodCall)
            {
                Nf.PutTag(nf__o, 9, 2);
                Nf.PutBytes(nf__o, TargetMethodCall);
            }
            if (HasTargetMethodParam)
            {
                Nf.PutTag(nf__o, 10, 2);
                Nf.PutBytes(nf__o, TargetMethodParam);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            BompTime = 0f;
            HasBompTime = false;
            BompRang = 0f;
            HasBompRang = false;
            BompPrefabPath = Nf.Empty;
            HasBompPrefabPath = false;
            BeAttackParticle = Nf.Empty;
            HasBeAttackParticle = false;
            BackNpcDis = 0f;
            HasBackNpcDis = false;
            BackHeroDis = 0f;
            HasBackHeroDis = false;
            MethodCall = Nf.Empty;
            HasMethodCall = false;
            MethodParam = Nf.Empty;
            HasMethodParam = false;
            TargetMethodCall = Nf.Empty;
            HasTargetMethodCall = false;
            TargetMethodParam = Nf.Empty;
            HasTargetMethodParam = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BompTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasBompTime = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BompRang = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasBompRang = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BompPrefabPath = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasBompPrefabPath = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BeAttackParticle = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasBeAttackParticle = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BackNpcDis = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasBackNpcDis = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BackHeroDis = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasBackHeroDis = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodCall = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodParam = true;
                        break;
                    }
                    case 9:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        TargetMethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasTargetMethodCall = true;
                        break;
                    }
                    case 10:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        TargetMethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasTargetMethodParam = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Bullet
    {
        public float EventTime = 0f;
        public bool HasEventTime = false;
        public int EventType = 0;
        public bool HasEventType = false;
        public float Speed = 0f;
        public bool HasSpeed = false;
        public float MaxDis = 0f;
        public bool HasMaxDis = false;
        public float BulletRang = 0f;
        public bool HasBulletRang = false;
        public int BulletBackType = 0;
        public bool HasBulletBackType = false;
        public float BackHeroDis = 0f;
        public bool HasBackHeroDis = false;
        public float BackNpcDis = 0f;
        public bool HasBackNpcDis = false;
        public int TacheDetroy = 0;
        public bool HasTacheDetroy = false;
        public byte[] BeAttackParticle = Nf.Empty;
        public bool HasBeAttackParticle = false;
        public byte[] FireTacheName = Nf.Empty;
        public bool HasFireTacheName = false;
        public FSVector3 FireTacheOffest = new FSVector3();
        public bool HasFireTacheOffest = false;
        public byte[] BulletPrefabPath = Nf.Empty;
        public bool HasBulletPrefabPath = false;
        public byte[] MethodCall = Nf.Empty;
        public bool HasMethodCall = false;
        public byte[] MethodParam = Nf.Empty;
        public bool HasMethodParam = false;
        public byte[] TargetMethodCall = Nf.Empty;
        public bool HasTargetMethodCall = false;
        public byte[] TargetMethodParam = Nf.Empty;
        public bool HasTargetMethodParam = false;
        public List<TacheBomp> Bomp = new List<TacheBomp>();
        public void Encode(MemoryStream nf__o)
        {
            if (HasEventTime)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, EventTime);
            }
            if (HasEventType)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)EventType);
            }
            if (HasSpeed)
            {
                Nf.PutTag(nf__o, 3, 5);
                Nf.PutF32(nf__o, Speed);
            }
            if (HasMaxDis)
            {
                Nf.PutTag(nf__o, 4, 5);
                Nf.PutF32(nf__o, MaxDis);
            }
            if (HasBulletRang)
            {
                Nf.PutTag(nf__o, 5, 5);
                Nf.PutF32(nf__o, BulletRang);
            }
            if (HasBulletBackType)
            {
                Nf.PutTag(nf__o, 6, 0);
                Nf.PutI64(nf__o, (long)BulletBackType);
            }
            if (HasBackHeroDis)
            {
                Nf.PutTag(nf__o, 7, 5);
                Nf.PutF32(nf__o, BackHeroDis);
            }
            if (HasBackNpcDis)
            {
                Nf.PutTag(nf__o, 8, 5);
                Nf.PutF32(nf__o, BackNpcDis);
            }
            if (HasTacheDetroy)
            {
                Nf.PutTag(nf__o, 9, 0);
                Nf.PutI64(nf__o, (long)TacheDetroy);
            }
            if (HasBeAttackParticle)
            {
                Nf.PutTag(nf__o, 10, 2);
                Nf.PutBytes(nf__o, BeAttackParticle);
            }
            if (HasFireTacheName)
            {
                Nf.PutTag(nf__o, 11, 2);
                Nf.PutBytes(nf__o, FireTacheName);
            }
            if (HasFireTacheOffest)
            {
                Nf.PutTag(nf__o, 12, 2);
                var nf__sub = new MemoryStream(); FireTacheOffest.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasBulletPrefabPath)
            {
                Nf.PutTag(nf__o, 13, 2);
                Nf.PutBytes(nf__o, BulletPrefabPath);
            }
            if (HasMethodCall)
            {
                Nf.PutTag(nf__o, 14, 2);
                Nf.PutBytes(nf__o, MethodCall);
            }
            if (HasMethodParam)
            {
                Nf.PutTag(nf__o, 15, 2);
                Nf.PutBytes(nf__o, MethodParam);
            }
            if (HasTargetMethodCall)
            {
                Nf.PutTag(nf__o, 16, 2);
                Nf.PutBytes(nf__o, TargetMethodCall);
            }
            if (HasTargetMethodParam)
            {
                Nf.PutTag(nf__o, 17, 2);
                Nf.PutBytes(nf__o, TargetMethodParam);
            }
            foreach (var nf__it in Bomp)
            {
                Nf.PutTag(nf__o, 18, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            EventTime = 0f;
            HasEventTime = false;
            EventType = 0;
            HasEventType = false;
            Speed = 0f;
            HasSpeed = false;
            MaxDis = 0f;
            HasMaxDis = false;
            BulletRang = 0f;
            HasBulletRang = false;
            BulletBackType = 0;
            HasBulletBackType = false;
            BackHeroDis = 0f;
            HasBackHeroDis = false;
            BackNpcDis = 0f;
            HasBackNpcDis = false;
            TacheDetroy = 0;
            HasTacheDetroy = false;
            BeAttackParticle = Nf.Empty;
            HasBeAttackParticle = false;
            FireTacheName = Nf.Empty;
            HasFireTacheName = false;
            FireTacheOffest = new FSVector3();
            HasFireTacheOffest = false;
            BulletPrefabPath = Nf.Empty;
            HasBulletPrefabPath = false;
            MethodCall = Nf.Empty;
            HasMethodCall = false;
            MethodParam = Nf.Empty;
            HasMethodParam = false;
            TargetMethodCall = Nf.Empty;
            HasTargetMethodCall = false;
            TargetMethodParam = Nf.Empty;
            HasTargetMethodParam = false;
            Bomp.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasEventTime = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEventType = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        Speed = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasSpeed = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MaxDis = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasMaxDis = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BulletRang = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasBulletRang = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BulletBackType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasBulletBackType = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BackHeroDis = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasBackHeroDis = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BackNpcDis = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasBackNpcDis = true;
                        break;
                    }
                    case 9:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        TacheDetroy = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasTacheDetroy = true;
                        break;
                    }
                    case 10:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BeAttackParticle = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasBeAttackParticle = true;
                        break;
                    }
                    case 11:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        FireTacheName = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasFireTacheName = true;
                        break;
                    }
                    case 12:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new FSVector3();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        FireTacheOffest = nf__m; HasFireTacheOffest = true;
                        break;
                    }
                    case 13:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BulletPrefabPath = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasBulletPrefabPath = true;
                        break;
                    }
                    case 14:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodCall = true;
                        break;
                    }
                    case 15:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodParam = true;
                        break;
                    }
                    case 16:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        TargetMethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasTargetMethodCall = true;
                        break;
                    }
                    case 17:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        TargetMethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasTargetMethodParam = true;
                        break;
                    }
                    case 18:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new TacheBomp();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        Bomp.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class BulletEvents
    {
        public List<Bullet> xBulletList = new List<Bullet>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in xBulletList)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            xBulletList.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Bullet();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xBulletList.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Move
    {
        public float EventTime = 0f;
        public bool HasEventTime = false;
        public int EventType = 0;
        public bool HasEventType = false;
        public float MoveDis = 0f;
        public bool HasMoveDis = false;
        public float MoveTime = 0f;
        public bool HasMoveTime = false;
        public byte[] MethodCall = Nf.Empty;
        public bool HasMethodCall = false;
        public byte[] MethodParam = Nf.Empty;
        public bool HasMethodParam = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasEventTime)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, EventTime);
            }
            if (HasEventType)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)EventType);
            }
            if (HasMoveDis)
            {
                Nf.PutTag(nf__o, 3, 5);
                Nf.PutF32(nf__o, MoveDis);
            }
            if (HasMoveTime)
            {
                Nf.PutTag(nf__o, 4, 5);
                Nf.PutF32(nf__o, MoveTime);
            }
            if (HasMethodCall)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, MethodCall);
            }
            if (HasMethodParam)
            {
                Nf.PutTag(nf__o, 6, 2);
                Nf.PutBytes(nf__o, MethodParam);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            EventTime = 0f;
            HasEventTime = false;
            EventType = 0;
            HasEventType = false;
            MoveDis = 0f;
            HasMoveDis = false;
            MoveTime = 0f;
            HasMoveTime = false;
            MethodCall = Nf.Empty;
            HasMethodCall = false;
            MethodParam = Nf.Empty;
            HasMethodParam = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasEventTime = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEventType = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MoveDis = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasMoveDis = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MoveTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasMoveTime = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodCall = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodParam = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AnimatorMoves
    {
        public List<Move> xMoveList = new List<Move>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in xMoveList)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            xMoveList.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Move();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xMoveList.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Camera
    {
        public float EventTime = 0f;
        public bool HasEventTime = false;
        public int EventType = 0;
        public bool HasEventType = false;
        public FSVector3 AmountParam = new FSVector3();
        public bool HasAmountParam = false;
        public float ShakeTime = 0f;
        public bool HasShakeTime = false;
        public byte[] MethodCall = Nf.Empty;
        public bool HasMethodCall = false;
        public byte[] MethodParam = Nf.Empty;
        public bool HasMethodParam = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasEventTime)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, EventTime);
            }
            if (HasEventType)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)EventType);
            }
            if (HasAmountParam)
            {
                Nf.PutTag(nf__o, 3, 2);
                var nf__sub = new MemoryStream(); AmountParam.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasShakeTime)
            {
                Nf.PutTag(nf__o, 4, 5);
                Nf.PutF32(nf__o, ShakeTime);
            }
            if (HasMethodCall)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, MethodCall);
            }
            if (HasMethodParam)
            {
                Nf.PutTag(nf__o, 6, 2);
                Nf.PutBytes(nf__o, MethodParam);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            EventTime = 0f;
            HasEventTime = false;
            EventType = 0;
            HasEventType = false;
            AmountParam = new FSVector3();
            HasAmountParam = false;
            ShakeTime = 0f;
            HasShakeTime = false;
            MethodCall = Nf.Empty;
            HasMethodCall = false;
            MethodParam = Nf.Empty;
            HasMethodParam = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasEventTime = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEventType = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new FSVector3();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        AmountParam = nf__m; HasAmountParam = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        ShakeTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasShakeTime = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodCall = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodParam = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class CameraControlEvents
    {
        public List<Camera> xCameraList = new List<Camera>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in xCameraList)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            xCameraList.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Camera();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xCameraList.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Particle
    {
        public float EventTime = 0f;
        public bool HasEventTime = false;
        public int Rotation = 0;
        public bool HasRotation = false;
        public byte[] ParticlePath = Nf.Empty;
        public bool HasParticlePath = false;
        public byte[] TargetTacheName = Nf.Empty;
        public bool HasTargetTacheName = false;
        public FSVector3 TargetTacheOffest = new FSVector3();
        public bool HasTargetTacheOffest = false;
        public int CastToSurface = 0;
        public bool HasCastToSurface = false;
        public int BindTarget = 0;
        public bool HasBindTarget = false;
        public float DestroyTime = 0f;
        public bool HasDestroyTime = false;
        public byte[] MethodCall = Nf.Empty;
        public bool HasMethodCall = false;
        public byte[] MethodParam = Nf.Empty;
        public bool HasMethodParam = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasEventTime)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, EventTime);
            }
            if (HasRotation)
            {
                Nf.PutTag(nf__o, 3, 0);
                Nf.PutI64(nf__o, (long)Rotation);
            }
            if (HasParticlePath)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, ParticlePath);
            }
            if (HasTargetTacheName)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, TargetTacheName);
            }
            if (HasTargetTacheOffest)
            {
                Nf.PutTag(nf__o, 6, 2);
                var nf__sub = new MemoryStream(); TargetTacheOffest.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
            if (HasCastToSurface)
            {
                Nf.PutTag(nf__o, 7, 0);
                Nf.PutI64(nf__o, (long)CastToSurface);
            }
            if (HasBindTarget)
            {
                Nf.PutTag(nf__o, 8, 0);
                Nf.PutI64(nf__o, (long)BindTarget);
            }
            if (HasDestroyTime)
            {
                Nf.PutTag(nf__o, 9, 5);
                Nf.PutF32(nf__o, DestroyTime);
            }
            if (HasMethodCall)
            {
                Nf.PutTag(nf__o, 10, 2);
                Nf.PutBytes(nf__o, MethodCall);
            }
            if (HasMethodParam)
            {
                Nf.PutTag(nf__o, 11, 2);
                Nf.PutBytes(nf__o, MethodParam);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            EventTime = 0f;
            HasEventTime = false;
            Rotation = 0;
            HasRotation = false;
            ParticlePath = Nf.Empty;
            HasParticlePath = false;
            TargetTacheName = Nf.Empty;
            HasTargetTacheName = false;
            TargetTacheOffest = new FSVector3();
            HasTargetTacheOffest = false;
            CastToSurface = 0;
            HasCastToSurface = false;
            BindTarget = 0;
            HasBindTarget = false;
            DestroyTime = 0f;
            HasDestroyTime = false;
            MethodCall = Nf.Empty;
            HasMethodCall = false;
            MethodParam = Nf.Empty;
            HasMethodParam = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasEventTime = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        Rotation = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasRotation = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        ParticlePath = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasParticlePath = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        TargetTacheName = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasTargetTacheName = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new FSVector3();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        TargetTacheOffest = nf__m; HasTargetTacheOffest = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        CastToSurface = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasCastToSurface = true;
                        break;
                    }
                    case 8:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        BindTarget = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasBindTarget = true;
                        break;
                    }
                    case 9:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        DestroyTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasDestroyTime = true;
                        break;
                    }
                    case 10:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodCall = true;
                        break;
                    }
                    case 11:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodParam = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class ParticleEvents
    {
        public List<Particle> xParticleList = new List<Particle>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in xParticleList)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            xParticleList.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Particle();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xParticleList.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Enable
    {
        public float EventTime = 0f;
        public bool HasEventTime = false;
        public int EventType = 0;
        public bool HasEventType = false;
        public byte[] TargetName = Nf.Empty;
        public bool HasTargetName = false;
        public byte[] MethodCall = Nf.Empty;
        public bool HasMethodCall = false;
        public byte[] MethodParam = Nf.Empty;
        public bool HasMethodParam = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasEventTime)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, EventTime);
            }
            if (HasEventType)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)EventType);
            }
            if (HasTargetName)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, TargetName);
            }
            if (HasMethodCall)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, MethodCall);
            }
            if (HasMethodParam)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, MethodParam);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            EventTime = 0f;
            HasEventTime = false;
            EventType = 0;
            HasEventType = false;
            TargetName = Nf.Empty;
            HasTargetName = false;
            MethodCall = Nf.Empty;
            HasMethodCall = false;
            MethodParam = Nf.Empty;
            HasMethodParam = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasEventTime = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEventType = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        TargetName = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasTargetName = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodCall = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodParam = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class EnableEvents
    {
        public List<Enable> xEnableList = new List<Enable>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in xEnableList)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            xEnableList.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Enable();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xEnableList.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Trail
    {
        public float EventTime = 0f;
        public bool HasEventTime = false;
        public int EventType = 0;
        public bool HasEventType = false;
        public byte[] TargetName = Nf.Empty;
        public bool HasTargetName = false;
        public byte[] MethodCall = Nf.Empty;
        public bool HasMethodCall = false;
        public byte[] MethodParam = Nf.Empty;
        public bool HasMethodParam = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasEventTime)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, EventTime);
            }
            if (HasEventType)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)EventType);
            }
            if (HasTargetName)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, TargetName);
            }
            if (HasMethodCall)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, MethodCall);
            }
            if (HasMethodParam)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, MethodParam);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            EventTime = 0f;
            HasEventTime = false;
            EventType = 0;
            HasEventType = false;
            TargetName = Nf.Empty;
            HasTargetName = false;
            MethodCall = Nf.Empty;
            HasMethodCall = false;
            MethodParam = Nf.Empty;
            HasMethodParam = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasEventTime = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEventType = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        TargetName = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasTargetName = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodCall = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodParam = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class TrailEvents
    {
        public List<Trail> xTrailList = new List<Trail>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in xTrailList)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            xTrailList.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Trail();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xTrailList.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Audio
    {
        public float EventTime = 0f;
        public bool HasEventTime = false;
        public int EventType = 0;
        public bool HasEventType = false;
        public byte[] AudioName = Nf.Empty;
        public bool HasAudioName = false;
        public byte[] MethodCall = Nf.Empty;
        public bool HasMethodCall = false;
        public byte[] MethodParam = Nf.Empty;
        public bool HasMethodParam = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasEventTime)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, EventTime);
            }
            if (HasEventType)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)EventType);
            }
            if (HasAudioName)
            {
                Nf.PutTag(nf__o, 3, 2);
                Nf.PutBytes(nf__o, AudioName);
            }
            if (HasMethodCall)
            {
                Nf.PutTag(nf__o, 4, 2);
                Nf.PutBytes(nf__o, MethodCall);
            }
            if (HasMethodParam)
            {
                Nf.PutTag(nf__o, 5, 2);
                Nf.PutBytes(nf__o, MethodParam);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            EventTime = 0f;
            HasEventTime = false;
            EventType = 0;
            HasEventType = false;
            AudioName = Nf.Empty;
            HasAudioName = false;
            MethodCall = Nf.Empty;
            HasMethodCall = false;
            MethodParam = Nf.Empty;
            HasMethodParam = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasEventTime = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEventType = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        AudioName = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasAudioName = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodCall = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodParam = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AudioEvents
    {
        public List<Audio> xAudioList = new List<Audio>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in xAudioList)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            xAudioList.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Audio();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xAudioList.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Speed
    {
        public float EventTime = 0f;
        public bool HasEventTime = false;
        public int EventType = 0;
        public bool HasEventType = false;
        public float SpeedValue = 0f;
        public bool HasSpeedValue = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasEventTime)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, EventTime);
            }
            if (HasEventType)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)EventType);
            }
            if (HasSpeedValue)
            {
                Nf.PutTag(nf__o, 3, 5);
                Nf.PutF32(nf__o, SpeedValue);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            EventTime = 0f;
            HasEventTime = false;
            EventType = 0;
            HasEventType = false;
            SpeedValue = 0f;
            HasSpeedValue = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasEventTime = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEventType = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        SpeedValue = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasSpeedValue = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class GlobalSpeeds
    {
        public List<Speed> xSpeedList = new List<Speed>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in xSpeedList)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            xSpeedList.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Speed();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xSpeedList.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class Fly
    {
        public float EventTime = 0f;
        public bool HasEventTime = false;
        public int EventType = 0;
        public bool HasEventType = false;
        public float MoveDis = 0f;
        public bool HasMoveDis = false;
        public float MoveTime = 0f;
        public bool HasMoveTime = false;
        public float MoveTopDis = 0f;
        public bool HasMoveTopDis = false;
        public byte[] MethodCall = Nf.Empty;
        public bool HasMethodCall = false;
        public byte[] MethodParam = Nf.Empty;
        public bool HasMethodParam = false;
        public void Encode(MemoryStream nf__o)
        {
            if (HasEventTime)
            {
                Nf.PutTag(nf__o, 1, 5);
                Nf.PutF32(nf__o, EventTime);
            }
            if (HasEventType)
            {
                Nf.PutTag(nf__o, 2, 0);
                Nf.PutI64(nf__o, (long)EventType);
            }
            if (HasMoveDis)
            {
                Nf.PutTag(nf__o, 3, 5);
                Nf.PutF32(nf__o, MoveDis);
            }
            if (HasMoveTime)
            {
                Nf.PutTag(nf__o, 4, 5);
                Nf.PutF32(nf__o, MoveTime);
            }
            if (HasMoveTopDis)
            {
                Nf.PutTag(nf__o, 5, 5);
                Nf.PutF32(nf__o, MoveTopDis);
            }
            if (HasMethodCall)
            {
                Nf.PutTag(nf__o, 6, 2);
                Nf.PutBytes(nf__o, MethodCall);
            }
            if (HasMethodParam)
            {
                Nf.PutTag(nf__o, 7, 2);
                Nf.PutBytes(nf__o, MethodParam);
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            EventTime = 0f;
            HasEventTime = false;
            EventType = 0;
            HasEventType = false;
            MoveDis = 0f;
            HasMoveDis = false;
            MoveTime = 0f;
            HasMoveTime = false;
            MoveTopDis = 0f;
            HasMoveTopDis = false;
            MethodCall = Nf.Empty;
            HasMethodCall = false;
            MethodParam = Nf.Empty;
            HasMethodParam = false;
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasEventTime = true;
                        break;
                    }
                    case 2:
                    {
                        if ((uint)(nf__key & 7) != 0)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        EventType = (int)nf__r.Varint();
                        if (!nf__r.Ok) return false;
                        HasEventType = true;
                        break;
                    }
                    case 3:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MoveDis = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasMoveDis = true;
                        break;
                    }
                    case 4:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MoveTime = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasMoveTime = true;
                        break;
                    }
                    case 5:
                    {
                        if ((uint)(nf__key & 7) != 5)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MoveTopDis = nf__r.F32();
                        if (!nf__r.Ok) return false;
                        HasMoveTopDis = true;
                        break;
                    }
                    case 6:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodCall = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodCall = true;
                        break;
                    }
                    case 7:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        MethodParam = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        HasMethodParam = true;
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }

    public class AnimatorFlys
    {
        public List<Fly> xFlyList = new List<Fly>();
        public void Encode(MemoryStream nf__o)
        {
            foreach (var nf__it in xFlyList)
            {
                Nf.PutTag(nf__o, 1, 2);
                var nf__sub = new MemoryStream(); nf__it.Encode(nf__sub);
                Nf.PutBytes(nf__o, nf__sub.ToArray());
            }
        }
        public byte[] Encode()
        {
            var nf__o = new MemoryStream(); Encode(nf__o); return nf__o.ToArray();
        }
        public void Clear()
        {
            xFlyList.Clear();
        }
        public bool Decode(byte[] nf__data, int nf__off, int nf__len)
        {
            Clear();
            var nf__r = new NfReader(nf__data, nf__off, nf__len);
            while (!nf__r.Done())
            {
                ulong nf__key = nf__r.Varint();
                if (!nf__r.Ok) return false;
                switch ((uint)(nf__key >> 3))
                {
                    case 1:
                    {
                        if ((uint)(nf__key & 7) != 2)
                        {
                            nf__r.Skip((uint)(nf__key & 7));
                            if (!nf__r.Ok) return false;
                            break;
                        }
                        var nf__sub = nf__r.Bytes();
                        if (!nf__r.Ok) return false;
                        var nf__m = new Fly();
                        if (!nf__m.Decode(nf__sub, 0, nf__sub.Length)) return false;
                        xFlyList.Add(nf__m);
                        break;
                    }
                    default:
                        nf__r.Skip((uint)(nf__key & 7));
                        if (!nf__r.Ok) return false;
                        break;
                }
            }
            return nf__r.Ok;
        }
    }
}
